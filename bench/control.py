#!/usr/bin/env python3
"""Read the control's compared numbers at a cell's own size, on a TPU.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--precision bf16]

The control is the plain reference with bfloat16 distance products put in
the program's place (`snnbench.control`); ``--precision f32`` puts the
float32 HIGHEST brute force there instead, which must pass.  One JSON line
per seed.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window the plan is made for "
                         "(default: BENCHMARK.json's run_seconds)")
    ap.add_argument("--precision", choices=("bf16", "f32"), default="bf16")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
    import jax

    from snnbench import control, spec

    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    seconds = args.seconds or spec.load_benchmark()["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control.numbers(args.workload, seed, seconds,
                              bf16=args.precision == "bf16")
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
