"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the JAX profiler's ``.xplane.pb`` into a small plain form:
per chip, the events of the device's "XLA Ops" line, and the host
annotations the benchmark itself wrote (``dispatch``, ``device_get``),
each as ``[name, start_ns, duration_ns]`` on
the trace's own clock.  ``reduce`` turns that form into a ``Summary``:

* busy: the union of the device-op intervals, averaged over the chips;
* leaf ops: an op whose interval encloses another op of the same line (a
  ``while``, a conditional or a call, whose body ops are in the trace
  too) is not a leaf, nor is a control-flow op whose body the trace cut
  off; only leaves are timed, so no time counts twice;
* kernels: found by a stable name, the op name with its HLO number
  stripped (``weighted_agg.7`` -> ``weighted_agg``), matched by substring;
* idle gaps: each interval in which no op runs, named by the host
  annotation that overlaps it most (``host`` where none does).
"""
from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path

HOST_NAMES = ("dispatch", "device_get")
_OP = re.compile(r"%?([^\s=]+)")
_NUM = re.compile(r"(\.\d+)+$")
_LAYOUT = re.compile(r"\{[^}]*\}?")
# control flow: an op of this kind times its body, whose ops are leaves
_ENCLOSING = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%copy.435 = f32[...] copy(...)`` -> ``copy.435``."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def stable_name(event_name: str) -> str:
    """The op name without its HLO number: ``weighted_agg.7`` ->
    ``weighted_agg``."""
    return _NUM.sub("", op_name(event_name))


def label(event_name: str) -> str:
    """Op name and result shape, for the breakdown:
    ``copy.435 f32[5,40000,164]``."""
    name = op_name(event_name)
    rest = event_name.partition("= ")[2]
    if not rest or rest.startswith("("):
        return name
    return f"{name} {_LAYOUT.sub('', rest.split(' ')[0])}"


def load(path: Path, host_names=HOST_NAMES) -> dict:
    """The plain form of an ``.xplane.pb`` trace."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.append([[e.name, e.start_ns, e.duration_ns]
                                   for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events if e.name in host_names]
    return {"device": device, "host": host}


def _leaves(events):
    """Indices of the events that enclose no other event of the line."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    parent = set()
    stack: list[int] = []
    for i in order:
        s, e = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            top = stack[-1]
            if e <= events[top][1] + events[top][2]:
                parent.add(top)
        stack.append(i)
    return [i for i in range(len(events)) if i not in parent
            and stable_name(events[i][0]) not in _ENCLOSING]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    op_s: dict              # op label -> seconds (leaves, summed over chips)
    kernel_ops: dict        # stable name -> (seconds, count)
    gaps: list              # (name, seconds), longest first

    def kernel(self, key: str):
        """(seconds, calls) of the leaf ops whose stable name contains
        ``key``, summed over chips; None where the trace has none."""
        hits = [v for k, v in self.kernel_ops.items() if key in k]
        if not hits:
            return None
        return sum(h[0] for h in hits), sum(h[1] for h in hits)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [list(g) for g in self.gaps[:n]]}


def reduce(form: dict) -> Summary:
    host = form["host"]
    chips = [evs for evs in form["device"] if evs]
    if not chips:
        raise ValueError("the trace holds no device op")
    starts = [e[1] for evs in chips for e in evs] + [h[1] for h in host]
    ends = ([e[1] + e[2] for evs in chips for e in evs]
            + [h[1] + h[2] for h in host])
    lo, hi = min(starts), max(ends)
    busy, op_s = 0.0, collections.Counter()
    kernel = collections.defaultdict(lambda: [0.0, 0])
    gaps = []
    for evs in chips:
        spans = _union([(e[1], e[1] + e[2]) for e in evs])
        busy += sum(e - s for s, e in spans)
        for i in _leaves(evs):
            name, _, dur = evs[i]
            op_s[label(name)] += dur * 1e-9
            k = kernel[stable_name(name)]
            k[0] += dur * 1e-9
            k[1] += 1
        edges = [lo] + [x for s, e in spans for x in (s, e)] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_gap_name(host, s, e), (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / len(chips),
                   op_s=dict(op_s),
                   kernel_ops={k: tuple(v) for k, v in kernel.items()},
                   gaps=gaps)


def _gap_name(host, s, e) -> str:
    best, name = 0.0, "host"
    for n, hs, hd in host:
        overlap = min(e, hs + hd) - max(s, hs)
        if overlap > best:
            best, name = overlap, n
    return name
