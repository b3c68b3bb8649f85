#!/usr/bin/env python3
"""Sweep an open-loop cell's offered rate on the chip, to find its knee.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds 8 --rates 100,200,400

Sets the cell up once and measures one window at each rate; one JSON line
per rate (p50, p99, the median latency of the first and last fifth of
the requests, which shows a growing backlog, and the mean service time).  The knee is the highest
rate whose p99 stays within the server's default SLO without a growing
backlog; the cell's traffic file then fixes 0.8 x knee.
"""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    from snnbench import harness

    try:
        harness.sweep(args.workload, args.seed,
                      [float(r) for r in args.rates.split(",")], args.seconds)
    except harness.Abort as e:
        print(f"sweep: cannot run: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
