"""Roofline-term extraction from compiled XLA artifacts.

* compute / memory terms come from ``compiled.cost_analysis()``;
* collective bytes are NOT in cost_analysis — we parse the optimized HLO text
  and sum *operand* sizes of every all-gather / all-reduce / reduce-scatter /
  all-to-all / collective-permute instruction.

Hardware model (TPU v5e per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (one link active per collective step assumed).
"""
from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?P<result>[^=]*?)\s*"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<variant>-start|-done)?\(")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m and m.group(1):
        return max(len(m.group(1).split(",")), 1)
    return 1


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device *operand* bytes per collective kind (optimized HLO module).

    The optimized HLO prints only result shapes; operand bytes are recovered
    from the op semantics: all-gather operand = result/G, reduce-scatter
    operand = result*G, others operand == result (G = replica group size).
    Async '-done' halves are skipped ('-start' already counted).
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or m.group("variant") == "-done":
            continue
        kind = m.group("kind")
        shapes = _SHAPE_RE.findall(m.group("result"))
        if not shapes:
            continue
        # '-start' results are (operand, destination, ...) tuples: take the last
        dtype, dims = shapes[-1]
        rb = _shape_bytes(dtype, dims)
        g = _group_size(line)
        if kind == "all-gather":
            b = rb // g
        elif kind == "reduce-scatter":
            b = rb * g
        else:
            b = rb
        out[kind] = out.get(kind, 0) + b
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                # per-device HLO flops
    hbm_bytes: float            # per-device bytes accessed
    coll_bytes: float           # per-device collective operand bytes
    coll_breakdown: dict
    n_devices: int
    model_flops: float          # analytic useful flops (GLOBAL)
    peak_memory_bytes: float = 0.0

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline step-time estimate: max of the three overlapping engines."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        total_hlo = self.flops * self.n_devices
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def mfu(self) -> float:
        """Model-flops utilization at the roofline step time."""
        denom = self.step_time * self.n_devices * PEAK_FLOPS
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.coll_bytes,
            "collective_breakdown": self.coll_breakdown,
            "n_devices": self.n_devices,
            "model_flops_global": self.model_flops,
            "peak_memory_bytes": self.peak_memory_bytes,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "roofline_step_time_s": self.step_time,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_at_roofline": self.mfu,
        }


def analyze(compiled, model_flops: float, n_devices: int) -> Roofline:
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    raw_bytes = float(ca.get("bytes accessed", 0.0))
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)
    mem = compiled.memory_analysis()
    peak = 0.0
    if mem is not None:
        peak = float(getattr(mem, "temp_size_in_bytes", 0)) + \
            float(getattr(mem, "argument_size_in_bytes", 0)) + \
            float(getattr(mem, "output_size_in_bytes", 0)) - \
            float(getattr(mem, "alias_size_in_bytes", 0))
    return Roofline(flops=flops, hbm_bytes=raw_bytes,
                    coll_bytes=float(sum(coll.values())),
                    coll_breakdown=coll, n_devices=n_devices,
                    model_flops=model_flops, peak_memory_bytes=peak)
