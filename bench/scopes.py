"""Device time per phase of the federated round, from the program's scopes.

The program names the phases of its jitted round with ``jax.named_scope``
(``draw``, ``d_step``, ``gradient_penalty``, ``g_step``, ``merge``,
``kernel_layout``), and the compiler keeps that name stack in each HLO
op's ``metadata={op_name=...}``; backward ops carry their forward's
scope as ``transpose(jvp(d_step))``.  The trace names each device op by
its instruction name in the compiled module, so the map from op to scope
comes from the compiled text of the program the cell ran: rebuilt from
the cell's inputs, lowered at its shapes and precision, and compiled
(a hit in the compile cache the run filled).  Only traced runs read it.

Each op counts toward its innermost listed scope, so a phase's time is
its self time: ``d_step`` without the ``gradient_penalty`` and
``kernel_layout`` inside it.  Leaf ops in no listed scope count as
``unscoped``.  A program without these scopes maps no op, and its
per-phase metrics read nothing.
"""
from __future__ import annotations

import re
import sys
import traceback

SCOPES = ("draw", "d_step", "gradient_penalty", "g_step", "merge",
          "kernel_layout")
UNSCOPED = "unscoped"

# a transformation's wrapper around a scope: jvp(d_step), vmap(draw),
# transpose(jvp(gradient_penalty)); jit(name) is a function, not a scope
_WRAPPED = re.compile(r"(?!jit\()[A-Za-z_]\w*\((.*)\)")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s.*?'
                    r'metadata=\{[^}]*?op_name="([^"]*)"', re.M)

_last: list = [None, None]     # (cell, its phase seconds): one per run


def _bare(part: str) -> str:
    m = _WRAPPED.fullmatch(part)
    while m:
        part = m.group(1)
        m = _WRAPPED.fullmatch(part)
    return part


def scope_of(op_name: str):
    """The innermost listed scope in an op's name stack, else None."""
    found = None
    for part in op_name.split("/"):
        bare = _bare(part)
        if bare in SCOPES:
            found = bare
    return found


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: scope}`` for each op of a compiled module's
    text whose metadata names a listed scope."""
    out = {}
    for name, stack in _INSTR.findall(hlo_text):
        s = scope_of(stack)
        if s is not None:
            out[name] = s
    return out


def scope_seconds(op_s: dict, op_scope: dict) -> dict:
    """Leaf-op seconds per scope, plus ``unscoped``; ``op_s`` is the trace
    summary's ``{op label: seconds}`` (label = op name, then shape)."""
    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    for label, sec in op_s.items():
        out[op_scope.get(label.split(" ")[0], UNSCOPED)] += sec
    return out


def program_text(cell) -> str:
    """Compiled text of the cell's ``FederatedProgram.run`` at the shapes
    and precision it ran with, rebuilt from the cell's inputs."""
    import jax
    from bench import harness
    from bench.system import schema
    from repro.fed import FederatedProgram, setup_federation
    m, t = cell.m, cell.t
    fe = setup_federation(cell.parts, schema(m, cell.kinds), cell.cfg,
                          seed=cell.seed & 0x7FFFFFFF,
                          weighting=t["weighting"])
    prog = FederatedProgram(cell.cfg, fe.spans, fe.cond_spans,
                            batch=m["batch_size"],
                            local_steps=t["local_steps"],
                            weighting=t["weighting"])
    states = jax.device_put(cell.snapshots[0])
    with jax.default_matmul_precision(
            harness.PRECISION[m["matmul_precision"]]):
        lowered = prog.run.lower(states, fe.tables, fe.S, fe.n_rows,
                                 cell.window_keys[0])
        return lowered.compile().as_text()


def phase_seconds(ctx):
    """``scope_seconds`` of the traced window, once per run; None where
    the program has none of the scopes or its text cannot be had."""
    cell = ctx["cell"]
    if _last[0] is not cell:
        try:
            op_scope = op_scopes(program_text(cell))
        except Exception:           # the reader must not fail the run
            print("bench.scopes: no compiled program text", file=sys.stderr)
            traceback.print_exc()
            op_scope = {}
        _last[:] = [cell, scope_seconds(ctx["trace"].op_s, op_scope)
                    if op_scope else None]
    return _last[1]


def us_per_round(ctx, scope: str):
    """Microseconds of device time in ``scope`` per global round of the
    window (summed over chips)."""
    if ctx["spec"]["traffic_mix"]["kind"] != "train":
        return None
    rounds = ctx["window"]["attempted"] * ctx["spec"]["traffic_mix"][
        "rounds_per_dispatch"]
    phases = phase_seconds(ctx)
    if phases is None or not rounds:
        return None
    return phases[scope] * 1e6 / rounds
