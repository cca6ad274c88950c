#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once on the chips of this machine.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of stdout is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with pyarrow beside
its limit.  Off the TPU, short of chips, or on a device kind that
``peaks.json`` lacks, it exits non-zero and prints no result.

``--control`` puts the lower-precision reference (or the float32 Q6
revenue) in the program's place: its run must come out not correct.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    from lib import cell

    result = cell.run(args.workload, args.seed, args.seconds, args.trace,
                      t_process=T_PROCESS, control=args.control)
    cell.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
