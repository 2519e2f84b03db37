"""Records ``data/train_trace_scoped.json`` on a TPU: two dispatches of the
adult training program at 5 rounds per dispatch, traced with the
program's spans on, trimmed like ``train_trace.json`` (ops of 3 us or
more plus every kernel and loop op), with the map from op name to the
program's scope (``bench.scopes``) and the program's ``repro.*`` host
spans on the trace's clock.

    python3 bench/tests/record_scoped_trace.py --seed 7 --out bench/tests/data/train_trace_scoped.json
"""
import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

ROUNDS, DISPATCHES = 5, 2
KEEP_NS = 3000
KEEP_NAMES = ("segment_activations", "weighted_agg", "while", "conditional",
              "call")
NAME_CHARS = 40


def program_spans(path) -> list:
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    return [[e.name, e.start_ns, e.duration_ns]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    import jax
    from bench import harness, scopes, tracing
    from repro import obs
    harness.enable_cache()
    spec = harness.cell_spec(harness.load_benchmark(), "adult-train-p5")
    spec["traffic_mix"]["rounds_per_dispatch"] = ROUNDS
    obs.enable()
    ann = jax.profiler.TraceAnnotation
    with jax.default_matmul_precision(
            harness.PRECISION[spec["model"]["matmul_precision"]]):
        cell = harness.kind_module("train").Cell(spec, args.seed)
        cell.setup()
        states = cell.states
        with harness.profiled(True) as files:
            for keys in cell.window_keys[:DISPATCHES]:
                with ann("dispatch"):
                    states, _ = cell._dispatch(states, keys)
            with ann("device_get"):
                jax.block_until_ready(states)
        op_scope = scopes.op_scopes(scopes.program_text(cell))
    form = tracing.load(files[0])
    program = program_spans(files[0])
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    keep = lambda e: (e[2] >= KEEP_NS or any(
        k in tracing.stable_name(e[0]) for k in KEEP_NAMES))
    device = [[[e[0][:NAME_CHARS], e[1], e[2]] for e in evs if keep(e)]
              for evs in form["device"]]
    names = {tracing.op_name(e[0]) for evs in device for e in evs}
    out = {"source": f"{jax.devices()[0].device_kind}, FederatedProgram.run "
                     f"(adult, 5 clients, {ROUNDS} rounds per dispatch), "
                     f"jax.profiler with obs on; {DISPATCHES} dispatches, "
                     f"ops of 3 us or more plus every kernel and loop op",
           "device": device, "host": form["host"],
           "program": program,
           "op_scope": {k: v for k, v in sorted(op_scope.items())
                        if k in names}}
    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(out))
    s = tracing.reduce(out)
    print(json.dumps({"events": sum(map(len, device)),
                      "phases": scopes.scope_seconds(s.op_s, out["op_scope"]),
                      "leaf_s": sum(s.op_s.values())}))


if __name__ == "__main__":
    main()
