"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload adult-train-p5 --seed 7 --seconds 10 --trace 0

Exits non-zero, and prints no result, without the chips the cell asks
for or without the system under test (``src/repro``).  The last line of
standard output is the result (JSON); the last lines of standard error
are the compared numbers beside their limits.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the TPU runtime logs under /tmp unless told otherwise; a run writes
    # only inside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness
    try:
        line = harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, v in line["limits"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
