"""The plain reference: a brute force over the same float32 rows, in float64.

It imports nothing of the program.  Each query row is compared with every
base row on the device in plain `jax.numpy` (float32 at HIGHEST precision,
in chunks of rows), which only narrows the candidates: every row whose
float32 squared distance lies within a generous slack of the threshold comes
back, and the decision is then made on the host in float64 from the float32
rows.  The slack (``SLACK``) is more than ten times the float32 rounding of
the device pass, so no row the float64 test would keep is lost.

Exactness, as `chip_smoke.py` states it: an answer may differ from the
float64 reference only for pairs whose squared distance lies within the
float32 band of the threshold, ``band = 2 (d + 2) 2**-24 ((|x| + |q|)**2 +
r**2)`` with the norms of the centered rows (plus ``4 (d + 6) 2**-24`` for
the angular metric, whose rows the program normalises in float32).  Any
other difference is a wrong row.  Returned squared distances are compared
too: ``dist_gap`` is the widest gap between a returned value and the float64
one, in units of that pair's band.

The same brute force, asked for bfloat16 products, gives the control's
answers (`BruteForce(bf16=True)`), and its k-th distances and counts
calibrate the traffic's radii (`traffic.py`).
"""
from __future__ import annotations

import functools
import time

import numpy as np

U = 2.0 ** -24
CHUNK = 64          # query rows per device call
SLACK = 1e-4        # candidate slack, relative to (|x|max + |q|)**2


def index_space(rows: np.ndarray, metric: str) -> np.ndarray:
    """Float64 rows in the Euclidean space the metric reduces to."""
    x = np.asarray(rows, np.float64)
    if metric == "euclidean":
        return x
    if metric == "angular":
        return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-30)
    raise ValueError(f"no reference for metric {metric!r}")


def native_radius(r2, metric: str) -> np.ndarray:
    """The native radius whose index-space squared radius is ``r2``."""
    r2 = np.asarray(r2, np.float64)
    if metric == "euclidean":
        return np.sqrt(r2)
    if metric == "angular":
        return np.arccos(np.clip(1.0 - r2 / 2.0, -1.0, 1.0))
    raise ValueError(f"no reference for metric {metric!r}")


# --------------------------------------------------------------------------- #
# Device brute force                                                          #
# --------------------------------------------------------------------------- #
def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


@functools.lru_cache(maxsize=None)
def _kernels(bf16: bool):
    jax, jnp = _jnp()

    def sq(q, x, xx):
        if bf16:
            s = jax.lax.dot_general(
                q.astype(jnp.bfloat16), x.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        else:
            s = jax.lax.dot_general(
                q, x, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        qq = jnp.sum(q * q, axis=1)
        return jnp.maximum(qq[:, None] + xx[None, :] - 2.0 * s, 0.0)

    def counts(q, x, xx, thr):
        return jnp.sum(sq(q, x, xx) <= thr[:, None], axis=1)

    def kth(q, x, xx, k):
        d2 = sq(q, x, xx)
        if jax.default_backend() == "tpu":
            vals, _ = jax.lax.approx_min_k(d2, k, recall_target=0.95)
            return vals
        return -jax.lax.top_k(-d2, k)[0]

    def nearest(q, x, xx, k):
        vals, ids = jax.lax.top_k(-sq(q, x, xx), k)
        return ids, -vals

    def mask_bits(q, x, xx, thr):
        return jnp.packbits(sq(q, x, xx) <= thr[:, None], axis=1)

    def select(q, x, xx, thr, cap):
        d2 = sq(q, x, xx)
        keep = d2 <= thr[:, None]
        ids = jax.vmap(lambda row: jnp.nonzero(row, size=cap,
                                               fill_value=-1)[0])(keep)
        vals = jnp.take_along_axis(d2, jnp.maximum(ids, 0), axis=1)
        return ids, vals

    return {"counts": jax.jit(counts),
            "kth": jax.jit(kth, static_argnums=3),
            "nearest": jax.jit(nearest, static_argnums=3),
            "select": jax.jit(select, static_argnums=4),
            "mask_bits": jax.jit(mask_bits)}


def _pow2(n: int, least: int = 16) -> int:
    cap = least
    while cap < n:
        cap *= 2
    return cap


class BruteForce:
    """Squared distances of query rows to every base row, on the device.

    Rows are moved to the metric's index space and centered on their own
    mean in float32; products run at HIGHEST precision, or in bfloat16 for
    the control.
    """

    def __init__(self, base: np.ndarray, metric: str, *, bf16: bool = False):
        jax, jnp = _jnp()
        self.metric = metric
        self.bf16 = bf16
        x = jnp.asarray(base, jnp.float32)
        if metric == "angular":
            x = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True),
                                1e-30)
        self.mu = jnp.mean(x, axis=0)
        self.x = x - self.mu
        self.xx = jnp.sum(self.x * self.x, axis=1)
        self.xmax = float(jnp.sqrt(jnp.max(self.xx)))
        self._k = _kernels(bf16)

    def free(self) -> None:
        self.x = self.xx = self.mu = None

    def _chunks(self, q: np.ndarray, *cols):
        """Padded (CHUNK, ...) device blocks of the centered query rows."""
        _, jnp = _jnp()
        qc = np.asarray(index_space(q, self.metric), np.float32)
        qc = jnp.asarray(qc) - self.mu
        m = qc.shape[0]
        for s in range(0, m, CHUNK):
            e = min(m, s + CHUNK)
            blk = jnp.zeros((CHUNK, qc.shape[1]), jnp.float32)
            blk = blk.at[:e - s].set(qc[s:e])
            pads = []
            for c in cols:
                p = np.full(CHUNK, -np.inf, np.float32)
                p[:e - s] = c[s:e]
                pads.append(jnp.asarray(p))
            yield s, e, blk, pads

    def qnorm(self, q: np.ndarray) -> np.ndarray:
        """Centered norms of the query rows (float64)."""
        qc = index_space(q, self.metric) - np.asarray(self.mu, np.float64)
        return np.sqrt(np.einsum("ij,ij->i", qc, qc))

    def counts(self, q: np.ndarray, r2: np.ndarray) -> np.ndarray:
        out = np.zeros(q.shape[0], np.int64)
        for s, e, blk, (thr,) in self._chunks(q, np.asarray(r2, np.float32)):
            out[s:e] = np.asarray(self._k["counts"](blk, self.x, self.xx,
                                                    thr))[:e - s]
        return out

    def kth(self, q: np.ndarray, k: int) -> np.ndarray:
        """(m, k) ascending squared distances of the k nearest base rows
        (approximate on the TPU, recall 0.95: calibration only)."""
        out = np.zeros((q.shape[0], k), np.float32)
        for s, e, blk, _ in self._chunks(q):
            out[s:e] = np.asarray(self._k["kth"](blk, self.x, self.xx,
                                                 k))[:e - s]
        return out

    def nearest(self, q: np.ndarray, k: int):
        """(ids (m, k), d2 (m, k)) of the k nearest base rows, exact top-k
        over this brute force's own distances."""
        ids = np.zeros((q.shape[0], k), np.int64)
        d2 = np.zeros((q.shape[0], k), np.float32)
        for s, e, blk, _ in self._chunks(q):
            i, v = self._k["nearest"](blk, self.x, self.xx, k)
            ids[s:e] = np.asarray(i)[:e - s]
            d2[s:e] = np.asarray(v)[:e - s]
        return ids, d2

    def candidates(self, q: np.ndarray, thr: np.ndarray) -> list:
        """Per row, the ids of the base rows with d2 <= thr (float32), as a
        bit mask packed on the device and unpacked on the host."""
        thr = np.asarray(thr, np.float32)
        out = []
        for s, e, blk, (t,) in self._chunks(q, thr):
            bits = np.asarray(self._k["mask_bits"](blk, self.x, self.xx, t))
            rows, byte = np.nonzero(bits[:e - s])
            hot = np.unpackbits(bits[rows, byte][:, None], axis=1)
            r, b = np.nonzero(hot)
            ids = byte[r].astype(np.int64) * 8 + b
            cut = np.searchsorted(rows[r], np.arange(e - s + 1))
            out.extend(ids[cut[j]:cut[j + 1]] for j in range(e - s))
        return out

    def within(self, q: np.ndarray, thr: np.ndarray):
        """Per row, (ids, d2) of the base rows with d2 <= thr (float32)."""
        thr = np.asarray(thr, np.float32)
        cnt = self.counts(q, thr)
        out = []
        for s, e, blk, (t,) in self._chunks(q, thr):
            cap = _pow2(int(cnt[s:e].max()) if e > s else 0)
            ids, vals = self._k["select"](blk, self.x, self.xx, t, cap)
            ids, vals = np.asarray(ids), np.asarray(vals)
            for j in range(e - s):
                c = int(cnt[s + j])
                out.append((ids[j, :c].astype(np.int64), vals[j, :c]))
        return out


# --------------------------------------------------------------------------- #
# Float64 decisions                                                           #
# --------------------------------------------------------------------------- #
class RefRow:
    """Float64 candidates of one query row: ids ascending, d2 and band."""

    __slots__ = ("ids", "d2", "cnorm", "qnorm", "extra")

    def __init__(self, ids, d2, cnorm, qnorm, extra):
        order = np.argsort(ids)
        self.ids, self.d2, self.cnorm = ids[order], d2[order], cnorm[order]
        self.qnorm, self.extra = qnorm, extra

    def band(self, r2: float, d: int) -> np.ndarray:
        return 2 * (d + 2) * U * ((self.cnorm + self.qnorm) ** 2 + r2) \
            + self.extra


class Reference:
    """Float64 answers for query rows over ``base`` (host float32 rows)."""

    def __init__(self, base: np.ndarray, metric: str, d: int):
        self.device_s = 0.0         # time in the device brute force
        self.base = base
        self.metric = metric
        self.d = d
        self.bf = BruteForce(base, metric)
        self.mu64 = np.asarray(self.bf.mu, np.float64)
        self.extra = 4 * (d + 6) * U if metric == "angular" else 0.0

    def free(self) -> None:
        self.bf.free()

    def _rows(self, q, found, block: int = 1 << 16) -> list[RefRow]:
        """Float64 distances and centered norms of every candidate, computed
        in blocks of candidates across rows."""
        q64 = index_space(q, self.metric)
        qn = self.bf.qnorm(q)
        sizes = np.array([ids.size for ids in found], np.int64)
        ids = np.concatenate(found) if found else np.zeros(0, np.int64)
        owner = np.repeat(np.arange(len(found)), sizes)
        d2 = np.empty(ids.size)
        cn = np.empty(ids.size)
        for s in range(0, ids.size, block):
            e = min(ids.size, s + block)
            x = index_space(self.base[ids[s:e]], self.metric)
            diff = x - q64[owner[s:e]]
            d2[s:e] = np.einsum("ij,ij->i", diff, diff)
            x -= self.mu64
            cn[s:e] = np.sqrt(np.einsum("ij,ij->i", x, x))
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        return [RefRow(ids[a:b], d2[a:b], cn[a:b], float(qn[j]), self.extra)
                for j, (a, b) in enumerate(zip(bounds[:-1], bounds[1:]))]

    def _slack(self, q) -> np.ndarray:
        return SLACK * (self.bf.xmax + self.bf.qnorm(q)) ** 2

    def balls(self, q: np.ndarray, r2: np.ndarray) -> list[RefRow]:
        """Candidates holding every row within ``r2`` (+ band) of each row."""
        thr = np.asarray(r2, np.float64) + self._slack(q)
        t = time.perf_counter()
        found = self.bf.candidates(q, thr)
        self.device_s += time.perf_counter() - t
        return self._rows(q, found)

    def knn(self, q: np.ndarray, k: int) -> list[RefRow]:
        """Candidates holding every row within the k-th distance (+ band)."""
        t = time.perf_counter()
        _, d2 = self.bf.nearest(q, k)
        thr = d2[:, -1].astype(np.float64) * (1 + SLACK) + self._slack(q)
        found = self.bf.candidates(q, thr)
        self.device_s += time.perf_counter() - t
        return self._rows(q, found)


class Tally:
    """What the comparison found, over every row it compared."""

    def __init__(self):
        self.rows = 0
        self.wrong_rows = 0
        self.dist_gap = 0.0
        self.band_pairs = 0
        self.band_flips = 0
        self.notes: list[str] = []

    def wrong(self, what: str) -> None:
        self.wrong_rows += 1
        if len(self.notes) < 20:
            self.notes.append(what)

    def _gap(self, got_sq, ref_d2, band) -> None:
        if got_sq.size:
            g = float(np.max(np.abs(got_sq - ref_d2) / band))
            self.dist_gap = max(self.dist_gap, g)

    def _match(self, ref: RefRow, ids, what: str):
        """Positions of ``ids`` in the reference row, or None when an id is
        unknown or repeated (then the row is wrong)."""
        ids = np.asarray(ids, np.int64)
        if np.unique(ids).size != ids.size:
            self.wrong(f"{what}: an id is returned twice")
            return None
        pos = np.searchsorted(ref.ids, ids)
        pos = np.minimum(pos, max(ref.ids.size - 1, 0))
        if ids.size and (ref.ids.size == 0 or (ref.ids[pos] != ids).any()):
            self.wrong(f"{what}: an id lies beyond the reference's reach")
            return None
        return pos

    def ball(self, ref: RefRow, r2: float, d: int, ids, sq, what: str):
        """A radius or join row: its neighbor set and squared distances."""
        self.rows += 1
        band = ref.band(r2, d)
        inside = ref.d2 <= r2 + band
        edge = inside & (ref.d2 >= r2 - band)
        pos = self._match(ref, ids, what)
        if pos is None:
            return
        got = np.zeros(ref.ids.size, bool)
        got[pos] = True
        self._gap(np.asarray(sq, np.float64), ref.d2[pos], band[pos])
        if (got & ~inside).any():
            self.wrong(f"{what}: {int((got & ~inside).sum())} ids outside "
                       f"the ball")
            return
        missing = inside & ~edge & ~got
        if missing.any():
            self.wrong(f"{what}: {int(missing.sum())} neighbors missing")
            return
        self.band_pairs += int(edge.sum())
        self.band_flips += int((edge & (got != (ref.d2 <= r2))).sum())

    def knn(self, ref: RefRow, k: int, d: int, ids, sq, what: str):
        """A kNN row: the k ids nearest, and their squared distances."""
        self.rows += 1
        if len(ids) != k:
            self.wrong(f"{what}: {len(ids)} ids for k={k}")
            return
        t = float(np.sort(ref.d2)[k - 1])
        band = ref.band(t, d)
        pos = self._match(ref, ids, what)
        if pos is None:
            return
        self._gap(np.asarray(sq, np.float64), ref.d2[pos], band[pos])
        if (ref.d2[pos] > t + band[pos]).any():
            self.wrong(f"{what}: an id is not among the {k} nearest")
            return
        got = np.zeros(ref.ids.size, bool)
        got[pos] = True
        if (~got & (ref.d2 < t - band)).any():
            self.wrong(f"{what}: a nearer neighbor is missing")
            return
        self.band_pairs += int((np.abs(ref.d2 - t) <= band).sum())
