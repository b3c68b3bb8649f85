"""CPU tests of the benchmark: ``python3 -m pytest bench/tests``.

They run on the CPU at tiny sizes; the program's kernels run in interpret
mode or on the CPU oracle lane.  JAX's compilation cache goes to a
temporary directory, not the checkout's.
"""
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench-test-cache-"))

# a tiny copy of each cell: the same generator, harness and comparison at
# a size the CPU answers in seconds
TINY_CONFIG = {"n": 3000, "query_pool": 300,
               "assumed": {"clusters": 10, "mixture_seed": 1}}
TINY_TRAFFIC = {
    "radius": {"rate_per_s": 30, "check_requests": 16, "warm_batches": [8],
               "request": {"kind": "radius", "rows": 1,
                           "neighbors": {"per": "all", "median": 16}}},
    "join": {"blocks": 4, "check_requests": 2,
             "request": {"kind": "join", "rows": 32,
                         "neighbors": {"per": "block", "median": 16}}},
    "knn": {"rate_per_s": 30, "check_requests": 16, "warm_batches": [4]},
}


def tiny(workload: str, lane: str = "oracle") -> dict:
    kind = ("join" if "join" in workload else
            "knn" if "knn" in workload else "radius")
    config = dict(TINY_CONFIG)
    if kind == "knn":
        # the kNN cell's configuration fixes its points: the seed rotates
        config["assumed"] = dict(config["assumed"], points_seed=3)
    return {"config": config, "traffic": dict(TINY_TRAFFIC[kind]),
            "server": {"backend": lane}}
