#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark of the SNN serving path.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  The cell is an
entry of ``BENCHMARK.json``'s ``workloads``; its configuration, traffic mix
and metric readers are files under ``bench/`` found by name
(`snnbench.spec`).  With ``--trace 0`` the last line of stdout reports the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window.  Without a TPU, or outside a checkout
that holds the program (``src/repro``), it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(HERE), "src")


def main() -> int:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: cannot run: no program under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from snnbench import harness

    return harness.main(t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
