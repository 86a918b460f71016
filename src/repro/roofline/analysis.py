"""Three-term roofline from the compiled dry-run (no wall clock on CPU):

    compute    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

`compiled.cost_analysis()` runs on the SPMD-*partitioned* module, so its
flops/bytes are per-chip; dividing per-chip quantities by per-chip peaks
is algebraically identical to the global form above.  Collective bytes
are not in cost_analysis: we parse the partitioned HLO and sum operand
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (async `-start` forms counted once, `-done` skipped).
"""
from __future__ import annotations

import re
from typing import Dict, Optional

#: Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google
#: Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
#: 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (``ici_bw`` is
#: one of its four links).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bw": 819e9,             # B/s
        "ici_bw": 50e9,              # B/s per link
        "hbm_bytes": 16 * 1024 ** 3,
    },
}

#: the chip the repo targets: what a host without a TPU prices for.
TARGET_KIND = "TPU v5 lite"
HW_V5E = PEAKS[TARGET_KIND]


def device_peaks() -> tuple:
    """``(device_kind, peaks, is_target)`` for the device this process
    runs on.  A TPU whose kind is not in :data:`PEAKS` is an error, not a
    default; a host without a TPU prices for :data:`TARGET_KIND` and says
    so (``is_target``)."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return TARGET_KIND, HW_V5E, True
    if dev.device_kind not in PEAKS:
        raise KeyError(
            f"no peak table for TPU device kind {dev.device_kind!r} "
            f"(known: {sorted(PEAKS)}); add its published peaks to "
            "roofline.analysis.PEAKS")
    return dev.device_kind, PEAKS[dev.device_kind], False

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_RESULT_RE = re.compile(r"=\s+\(?([a-z0-9]+)\[([0-9,]*)\]")


def _participants(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))  # [n_groups, group_size]<=[N]
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Per-op-kind OPERAND bytes summed over the module (per device).

    The optimized-HLO printer omits operand types, so operand bytes are
    derived from the result shape: all-reduce / all-to-all /
    collective-permute have operand == result; all-gather's operand is
    result / participants; reduce-scatter's operand is result ×
    participants.  Async `-start` forms counted once, `-done` skipped.
    """
    out = {k: 0 for k in _COLL_OPS}
    for line in hlo_text.splitlines():
        for op in _COLL_OPS:
            if f" {op}(" not in line and f" {op}-start(" not in line:
                continue
            rm = _RESULT_RE.search(line)
            if rm is None:
                continue
            result = _shape_bytes(rm.group(1), rm.group(2))
            if result == 0:
                # tuple results (e.g. fused all-reduce of several tensors):
                # sum every shape on the left of the op name
                lhs = line.split(f" {op}", 1)[0]
                result = sum(_shape_bytes(dt, dims)
                             for dt, dims in _SHAPE_RE.findall(lhs))
            p = _participants(line)
            if op == "all-gather":
                operand = result // max(p, 1)
            elif op == "reduce-scatter":
                operand = result * p
            else:
                operand = result
            out[op] += operand
            break
    out["total"] = sum(out[k] for k in _COLL_OPS)
    return out


def extract_cost(cost: Optional[dict]) -> Dict[str, float]:
    """Normalize compiled.cost_analysis() output across backends."""
    c = cost or {}
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    if "bytes" in c:  # already normalized
        return {"flops": float(c.get("flops", 0.0)),
                "bytes": float(c["bytes"])}
    flops = float(c.get("flops", 0.0))
    bytes_accessed = float(c.get("bytes accessed", 0.0))
    if bytes_accessed == 0.0:
        bytes_accessed = sum(
            float(v) for k, v in c.items()
            if isinstance(k, str) and k.startswith("bytes accessed")
        )
    return {"flops": flops, "bytes": bytes_accessed}


def roofline_terms(cost: dict, coll_bytes_per_dev: int, *,
                   hw: dict = HW_V5E) -> Dict[str, float]:
    """All terms in SECONDS (per-chip quantities over per-chip peaks)."""
    c = extract_cost(cost)
    t_compute = c["flops"] / hw["peak_flops_bf16"]
    t_memory = c["bytes"] / hw["hbm_bw"]
    t_coll = coll_bytes_per_dev / hw["ici_bw"]
    dom = max(
        ("compute", t_compute), ("memory", t_memory),
        ("collective", t_coll), key=lambda kv: kv[1],
    )[0]
    total = max(t_compute, t_memory, t_coll)
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "bottleneck": dom,
        "bound_s": total,
        "hlo_flops_per_dev": c["flops"],
        "hlo_bytes_per_dev": c["bytes"],
        "coll_bytes_per_dev": float(coll_bytes_per_dev),
    }


def model_flops(cfg, n_params_active: int, tokens: int,
                kind: str = "train") -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N·D (inference forward)."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params_active * tokens
