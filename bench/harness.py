"""The benchmark harness: finds a cell's files by name, runs it, prints one line.

Everything a cell needs sits in files named after it, so a later change
adds a cell, a configuration or a per-layer metric by adding a file:

* ``BENCHMARK.json``: the cells and metrics (the contract);
* ``bench/configs/<config>.json``: the configuration as it is run;
* ``bench/traffic/<traffic>.json``: a traffic mix, its kind and its
  parameters;
* ``bench/workloads/<cell>.json``: the limits of the cell's check;
* ``bench/kinds/<kind>.py``: what the window drives (a ``Cell`` class);
* ``bench/metrics/<metric>.py``: one reader per per-layer metric,
  ``read(ctx) -> float | None``.

A run: name the device and fail without the chips the cell asks for;
build everything from the seed and warm the cell's shapes (set-up); time
``--seconds`` with no compile inside; compare what the timed path
produced with the plain reference; print the limits to stderr and the
result line last on stdout.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import re
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
# the configuration's stated matmul precision -> jax's name, and the
# control: the reference one precision down (three bf16 passes for float32)
PRECISION = {"float32": "float32"}
CONTROL = {"float32": "bf16_3x"}


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad files)."""


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise BenchError(f"{what} {name!r}: a name is 1-64 of "
                         f"[A-Za-z0-9_.-], starting with [A-Za-z0-9_]")
    return name


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing {path.relative_to(ROOT)}") from None


def load_benchmark(root: Path = ROOT) -> dict:
    """BENCHMARK.json with every name and unit checked."""
    bm = load_json(root / "BENCHMARK.json")
    for c in bm["configs"]:
        check_name(c["name"], "config")
        for k in c["reduced"]:
            check_name(k, "reduced key")
    for w in bm["workloads"]:
        for k in ("name", "config", "traffic"):
            check_name(w[k], f"workload {k}")
    for m in bm["end_to_end"] + bm["per_layer"]:
        check_name(m["name"], "metric")
        if not isinstance(m["unit"], str) or not UNIT.fullmatch(m["unit"]):
            raise BenchError(f"metric {m['name']}: unit {m['unit']!r} is not "
                             f"1-16 of [A-Za-z0-9_/%.-]")
    return bm


def metrics_of(bm: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` (end_to_end / per_layer) this cell reports."""
    return [m for m in bm[group]
            if "workloads" not in m or cell in m["workloads"]]


def cell_spec(bm: dict, cell: str) -> dict:
    """The workload entry with its configuration, traffic and limits."""
    check_name(cell, "workload")
    entry = next((w for w in bm["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise BenchError(f"no workload {cell!r} in BENCHMARK.json")
    conf = next(c for c in bm["configs"] if c["name"] == entry["config"])
    spec = dict(entry)
    spec["model"] = load_json(ROOT / conf["file"])
    spec["traffic_mix"] = load_json(BENCH / "traffic" /
                                    f"{entry['traffic']}.json")
    spec["limits"] = load_json(BENCH / "workloads" / f"{cell}.json")["limits"]
    return spec


def load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def kind_module(kind: str):
    return load_module(BENCH / "kinds" / f"{check_name(kind, 'kind')}.py",
                       f"bench_kind_{kind}")


def reader(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py",
                       f"bench_metric_{metric.replace('.', '_')}")


class CompileClock:
    """Counts jit traces and backend compiles as JAX reports them."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.events: list[tuple[float, str, float]] = []

        def on(name, secs, **_):
            if name in self.EVENTS:
                self.events.append((time.perf_counter(), name, secs))
        jax.monitoring.register_event_duration_secs_listener(on)

    def count(self, t0: float, t1: float) -> int:
        return sum(1 for t, _, _ in self.events if t0 <= t <= t1)


def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if require_chip and d.platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {d.platform}")
    if require_chip and len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def kernel_fallbacks() -> int:
    """Fused stages that took the jnp reference route or ran in the Pallas
    interpreter: on the chip every stage has to run its Mosaic kernel."""
    from repro.kernels import ops
    refs = sum(v for k, v in ops.DISPATCH_COUNTS.items() if k.endswith("_ref"))
    return refs + sum(ops.INTERPRET_COUNTS.values())


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table["devices"][kind]


def enable_cache():
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a cell's programs all stay (a machine's own limit,
    # where one is set in the environment, would evict them mid-run)
    jax.config.update("jax_compilation_cache_max_size", -1)


@contextlib.contextmanager
def profiled(enabled: bool):
    """Profiler trace around the block; yields the list that receives the
    trace file's path."""
    import jax
    out: list[Path] = []
    if not enabled:
        yield out
        return
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        out.extend(sorted(TRACE_DIR.rglob("*.xplane.pb")))


def run(cell: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, bm: dict | None = None,
        patch_spec=None) -> dict:
    """One run of ``cell``; returns the result line as a dict and the
    compared numbers under ``limits``."""
    if not (SRC / "repro").is_dir():
        raise BenchError(f"the system under test is missing: no {SRC}/repro")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    bm = bm or load_benchmark()
    spec = cell_spec(bm, cell)
    if patch_spec is not None:
        patch_spec(spec)
    import jax
    device = device_info(spec["chips"], require_chip)
    if require_chip:
        enable_cache()
    precision = spec["model"]["matmul_precision"]
    kinds = kind_module(spec["traffic_mix"]["kind"])
    clock = CompileClock()
    with jax.default_matmul_precision(PRECISION[precision]):
        c = kinds.Cell(spec, seed)
        c.setup()
        if trace:       # a trace is large: a few seconds of the window
            seconds = min(seconds, spec["traffic_mix"]["trace_seconds"])
        # set-up's garbage is collected in set-up; the collector stays on
        # in the window, as in any process that trains
        gc.collect()
        setup_s = time.perf_counter() - t_start
        t0 = time.perf_counter()
        with profiled(trace) as files:
            window = c.window(seconds, trace)
        t1 = time.perf_counter()
        window["compiles"] = clock.count(t0, t1)
        device["memory_peak_bytes"] = memory_peak()
        c.free()
        limits = c.check()
    if window["compiles"]:
        limits["compiles_in_window"] = (float(window["compiles"]), 0.0)
    if require_chip:
        limits["kernel_fallbacks"] = (float(kernel_fallbacks()), 0.0)
    correct = all(math.isfinite(v) and v <= lim
                  for v, lim in limits.values()) and window["failed"] == 0
    metrics = {}
    breakdown = None
    if trace:
        from bench import tracing
        summary = tracing.reduce(tracing.load(files[0]))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = {"cell": c, "spec": spec, "window": window, "trace": summary,
               "peaks": peaks_for(device["kind"]) if require_chip else None}
        for m in metrics_of(bm, cell, "per_layer"):
            value = reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = summary.breakdown()
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        for m in metrics_of(bm, cell, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": window["attempted"],
            "failed": window["failed"], "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["limits"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in limits.items()}
    return line
