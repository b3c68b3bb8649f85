"""With the timed path broken underneath, a run must come out not correct.

Each test skips only the look for a chip, and drives the rest of a run at a
tiny size through `SNNServer`, with the program's emission of answers
(`TenantRuntime._emit`, where every answer is produced) altered.  The cells
have no training state and no exchange between chips, so the faults that
apply are an answer altered where it is produced and half of a batch left
out.  A sound run is the baseline.
"""
import time

import numpy as np
import pytest

from conftest import tiny
from repro.serving import runtime
from snnbench import harness


def _altered(self, req, *, indices, sq_dists, **kw):
    """Every answer with one neighbor id replaced by a wrong one."""
    idx = np.array(indices, copy=True)
    if idx.size:
        idx[-1] = (idx[-1] + 1) % 3000
    return _EMIT(self, req, indices=idx, sq_dists=sq_dists, **kw)


def _half_left_out(self, req, *, indices, sq_dists, indptr=None, **kw):
    """Half of the batch's rows answered with nothing."""
    if indptr is not None:          # a join block: drop its second half
        m = indptr.size - 1
        cut = indptr[m // 2]
        indptr = np.minimum(indptr, cut)
        return _EMIT(self, req, indices=indices[:cut],
                     sq_dists=sq_dists[:cut], indptr=indptr, **kw)
    if req.id % 2:                  # single rows: every second request
        return _EMIT(self, req, indices=indices[:0], sq_dists=sq_dists[:0],
                     indptr=indptr, **kw)
    return _EMIT(self, req, indices=indices, sq_dists=sq_dists,
                 indptr=indptr, **kw)


_EMIT = runtime.TenantRuntime._emit
FAULTS = {"sound": None, "altered": _altered, "half": _half_left_out}


def _run(name, monkeypatch, fault):
    if FAULTS[fault] is not None:
        monkeypatch.setattr(runtime.TenantRuntime, "_emit", FAULTS[fault])
    args = harness.parse(["--workload", name, "--seed", "4000000007",
                          "--seconds", "1", "--trace", "0"])
    return harness.run(args, time.perf_counter(), require_tpu=False,
                       overrides=tiny(name))


@pytest.mark.parametrize("fault", ["sound", "altered", "half"])
@pytest.mark.parametrize("name", ["sift1m.radius-open", "sift1m.join-backlog",
                                  "glove100.knn-open"])
def test_fault_is_caught(name, fault, monkeypatch):
    out = _run(name, monkeypatch, fault)
    assert out["correct"] is (fault == "sound"), out["checks"]
