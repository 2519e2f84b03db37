"""Readings that the limits of a cell's check are set from.

    python3 bench/readings.py --workload adult-train-p5 --seeds 1,2,3 \
        --control-seeds 1,2 --out readings.jsonl

For each seed, in one process (so compiled programs are shared): the
cell's set-up and checked dispatches, then the compared numbers of the
program against the reference (sound readings).  For each control seed
also the same numbers with the reference computed one precision lower
in the program's place (control readings), and with each fault of
``compare.REFERENCE_FAULTS`` planted in the reference in the program's
place (fault readings).  With ``--fault`` the program is broken
underneath first (``bench/faults.py``) and the sound readings are that
fault's.  One JSON line per seed.  The benchmark's own runs never run
this; it needs the chip like they do.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import compare, harness
    sys.path.insert(0, str(harness.SRC))
    import jax
    bm = harness.load_benchmark()
    spec = harness.cell_spec(bm, args.workload)
    harness.device_info(spec["chips"], True)
    harness.enable_cache()
    if args.fault:
        from bench import faults
        getattr(faults, args.fault)(setattr)
    kinds = harness.kind_module(spec["traffic_mix"]["kind"])
    stated = spec["model"]["matmul_precision"]
    low = harness.CONTROL[stated]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    with out.open("a") as f, jax.default_matmul_precision(
            harness.PRECISION[stated]):
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            cell = kinds.Cell(spec, seed)
            cell.setup()
            cell.free()
            t1 = time.perf_counter()
            row = {"workload": args.workload, "seed": seed,
                   "fault": args.fault, "setup_s": t1 - t0,
                   "sound": cell.readings()}
            row["check_s"] = time.perf_counter() - t1
            if seed in controls:
                row["control"] = cell.readings("control", low)
                for fault in compare.REFERENCE_FAULTS:
                    row[fault] = cell.readings(fault)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
