"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by
name from ``BENCHMARK.json`` (see ``bench/harness.py``).  The run needs
the TPU chips the cell asks for: without them it prints no result and
exits 3.  ``--rehearse`` is for tests only: it runs the cell's code
path on whatever JAX has (the CPU here), at the configuration's
rehearsal sizes and for two rounds, and writes no device metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tests only: run on the CPU at rehearsal sizes")
    args = ap.parse_args(argv)

    from bench import harness

    cell = harness.load_cell(args.workload)
    try:
        result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), rehearse=args.rehearse,
                             t_process=T_PROCESS)
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
