"""Roofline cost model for adaptive kernel routing (planner ``mode="auto"``).

The PR-1 planner routed *every* matched pattern onto a Pallas kernel;
that wins where the kernel restructures the computation (group-by as
one-hot MXU matmuls) but loses where it merely re-expresses an already
memory-bound jnp lowering plus launch/padding overhead (tiny inputs,
large-key vecmerger scatter).  Following Split Annotations' observation
that a cost-gated scheduler is what makes transparent acceleration safe
to enable by default, every candidate ``KernelCall`` is priced twice —
kernel route vs. generic jnp lowering — through the roofline constants
in :mod:`repro.roofline.analysis` and routed only when the kernel is
not meaningfully worse.

Each estimate is ``max(bytes/HBM_bw, flops/peak)`` plus route-specific
overheads:

* **padding** — kernels pad every column to a block multiple, so a tiny
  input pays for a whole tile of traffic;
* **launch** — a Pallas dispatch has fixed overhead the inlined jnp
  lowering does not pay;
* **scratch** — materialized helpers (one-hot tiles, stacked value
  matrices, compaction sorts) are charged to the kernel route;
* **structure factors** — the generic lowering pays for accumulator
  machinery (mask broadcasts, select chains, per-aggregate passes) and
  for sort-based keyed aggregation; scatter stores pay a random-access
  penalty.  These are calibrated against the PR-1 ablation
  (``benchmarks/bench_kernelplan.py``): segment-style group-by ~2.5-3.8x
  in favor of the kernel, vecmerger scatter in favor of jnp.

The absolute seconds are TPU-roofline numbers, not CPU wall clock; only
the *ordering* of the two estimates drives routing, and the overhead
terms are what flip it at the observed crossover points.

``estimate(spec, meta)`` returns a :class:`CostEstimate`; ``meta`` is
the planner-collected static description of the match (sizes from
``Iter`` hints, op counts from the staged bodies).  Unknown sizes
reject conservatively: a route we cannot price is a route we do not
take (the jnp lowering is always correct).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from math import ceil, log2
from typing import Optional

from ...roofline.analysis import device_peaks

#: route when kernel_s <= jnp_s * (1 + ROUTE_MARGIN): prefer the kernel
#: on a near-tie (it strictly reduces HBM traffic on the real target).
ROUTE_MARGIN = 0.10

#: fixed per-launch overhead of a Pallas dispatch (grid setup + the
#: kernel's own jit boundary) that the inlined jnp lowering never pays.
LAUNCH_OVERHEAD_S = 1e-6

#: generic-emitter accumulator machinery (mask broadcast, select chain,
#: finalize combine) as a multiplicative tax on the jnp reduce lowering.
REDUCE_STRUCTURE_TAX = 1.15

#: random-access scatter stores achieve a fraction of streaming HBM
#: bandwidth; .at[].add is modelled as this many streaming passes.
SCATTER_PENALTY = 4.0

#: sort-based keyed aggregation (the generic dictmerger lowering) moves
#: roughly key+val+packed rows per comparison level; this scales the
#: n*log2(n) byte volume.
SORT_BYTES_PER_ROW = 24.0

#: deep elementwise chains risk XLA materializing intermediates between
#: fusion islands; per-op slack on the jnp map-chain estimate.
MAP_CHAIN_SLACK_PER_OP = 0.10

#: the one-hot segment kernels' VMEM accumulator bound (segment_reduce
#: MAX_K): keyed accumulation beyond it serves the ref scatter instead.
SEGMENT_TILE_K = 4096

#: int32 elementwise operations per second of the TPU's vector unit
#: (8 sublanes x 128 lanes x 4 ALUs at 1.5 GHz: an estimate, no
#: published peak), which prices VPU-bound kernels such as the exact
#: group route.
VPU_INT_OPS = 8 * 128 * 4 * 1.5e9

#: a vectorized binary search (the generic dict-probe lowering) issues
#: log2(K) dependent random loads per row; each achieves this many
#: streaming-pass equivalents (gentler than full scatter: the upper tree
#: levels stay cache/VMEM resident).
BSEARCH_PENALTY = 2.0


@dataclass(frozen=True)
class CostEstimate:
    """Priced routing decision for one matched pattern."""

    kernel_s: float
    jnp_s: float
    routed: bool
    why: str
    #: where the kernel-side figure came from: "roofline" (analytic
    #: constants) or "measured" (cost-ledger median via kernelplan
    #: calibration).
    source: str = "roofline"

    def as_stats(self) -> dict:
        return {
            "kernel_us": round(self.kernel_s * 1e6, 3),
            "jnp_us": round(self.jnp_s * 1e6, 3),
            "routed": self.routed,
            "why": self.why,
            "source": self.source,
        }


REJECT_UNKNOWN = CostEstimate(
    float("inf"), 0.0, False,
    "unknown size: cannot price the kernel route, falling back to jnp",
)


def _roofline_s(bytes_moved: float, flops: float) -> float:
    _, hw, _ = device_peaks()
    return max(bytes_moved / hw["hbm_bw"], flops / hw["peak_flops_bf16"])


def _peaks_tag() -> str:
    """Which chip's peaks priced the estimate (a target off the TPU)."""
    kind, _, is_target = device_peaks()
    return f"peaks={kind}" + (" (target)" if is_target else "")


def _pad(n: int, block: int) -> int:
    return int(ceil(max(n, 1) / block)) * block


def _decide(kernel_s: float, jnp_s: float, why: str) -> CostEstimate:
    routed = kernel_s <= jnp_s * (1.0 + ROUTE_MARGIN)
    return CostEstimate(kernel_s, jnp_s, routed, why)


# ---------------------------------------------------------------------------
# Per-pattern cost hooks (wired onto KernelSpec.cost in registry.py).
# Every hook takes the planner's `meta` dict and returns a CostEstimate.
# ---------------------------------------------------------------------------


def cost_filter_reduce(meta: dict) -> CostEstimate:
    """Predicated multi-aggregate sum: one shared pass vs. the generic
    merger accumulators.  Gate is padding + launch vs. structure tax."""
    n = meta.get("n")
    if not n:
        return REJECT_UNKNOWN
    cols = max(meta.get("cols", 1), 1)
    ops = meta.get("ops", 1)
    aggs = max(meta.get("n_aggs", 1), 1)
    e = meta.get("elem_bytes", 8)
    block = meta.get("block", 8 * 1024)
    np_ = _pad(n, block)
    # kernel: every column + the predicate mask stream once, padded;
    # the multi-agg variant shares the mask/column loads across outputs.
    k_bytes = np_ * (cols * e + 1) + aggs * e
    k_flops = np_ * (ops + aggs)
    kernel_s = _roofline_s(k_bytes, k_flops) + LAUNCH_OVERHEAD_S
    j_bytes = (n * (cols * e + 1)) * REDUCE_STRUCTURE_TAX
    j_flops = n * (ops + aggs)
    jnp_s = _roofline_s(j_bytes, j_flops)
    return _decide(kernel_s, jnp_s,
                   f"n={n} cols={cols} aggs={aggs} pad={np_ - n}")


def cost_vecmerger(meta: dict) -> CostEstimate:
    """Scatter-add vs. one-hot MXU segment sum.  The kernel's 2*n*K
    matmul FLOPs cross the scatter's memory bound as K grows; beyond the
    VMEM tile bound the 'kernel' route degenerates to the same scatter
    plus overhead, so it can never win there."""
    n, k = meta.get("n"), meta.get("k")
    if not n or not k:
        return REJECT_UNKNOWN
    e = meta.get("elem_bytes", 8)
    block = meta.get("block", 512)
    max_k = meta.get("max_k")
    np_ = _pad(n, block)
    j_bytes = n * (8 + 2 * e) * SCATTER_PENALTY + k * e
    jnp_s = _roofline_s(j_bytes, n)
    if max_k is not None and k > max_k:
        # kops falls back to the ref segment-sum (itself a scatter):
        # strictly the jnp cost plus dispatch — never routable.
        return _decide(jnp_s * 1.2 + LAUNCH_OVERHEAD_S, jnp_s,
                       f"n={n} K={k} exceeds VMEM tile bound {max_k}")
    k_bytes = np_ * (4 + e) + k * e
    k_flops = 2.0 * np_ * k
    kernel_s = _roofline_s(k_bytes, k_flops) + LAUNCH_OVERHEAD_S
    return _decide(kernel_s, jnp_s, f"n={n} K={k} pad={np_ - n}")


def cost_dict_group(meta: dict) -> CostEstimate:
    """Dense-int-key group-by: one-hot segment sums (or, for integer
    values, the exact word kernel) + compaction vs. the generic
    sort-based dictmerger lowering."""
    n, k = meta.get("n"), meta.get("k")
    if not n or not k:
        return REJECT_UNKNOWN
    e = meta.get("elem_bytes", 8)
    block = meta.get("block", 256)
    np_ = _pad(n, block)
    j_bytes = n * SORT_BYTES_PER_ROW * max(log2(max(n, 2)), 1.0)
    jnp_s = _roofline_s(j_bytes, n)
    words = meta.get("words")
    if words:
        # the words actually moved: the input columns read once, the
        # int32 plane stack (key + one plane per two words) written and
        # read back; every word of every row is a compare-select and an
        # add per group on the VPU
        planes = 1 + (words - 1) // 2
        k_bytes = n * meta.get("cols", 1) * 4 + 2 * np_ * planes * 4
        vpu_s = 2.0 * np_ * k * words / VPU_INT_OPS
        kernel_s = (max(_roofline_s(k_bytes, 0.0), vpu_s)
                    + 2 * LAUNCH_OVERHEAD_S)
        return _decide(kernel_s, jnp_s,
                       f"n={n} K={k} words={words} pad={np_ - n}")
    # kernel: stacked (vals, ones) scratch + one-hot matmul + K-compaction
    k_bytes = np_ * (4 + 2 * e) + 2 * n * e + 4 * k * e
    k_flops = 2.0 * np_ * k * 2 + k * max(log2(max(k, 2)), 1.0)
    kernel_s = _roofline_s(k_bytes, k_flops) + 2 * LAUNCH_OVERHEAD_S
    return _decide(kernel_s, jnp_s, f"n={n} K={k} pad={np_ - n}")


def cost_hash_build(meta: dict) -> CostEstimate:
    """Open-addressing dict build (hash-to-slot + one-hot accumulation +
    compaction) vs. the generic sort-based dictmerger lowering.  The
    serial insert chain is random-access bound; the sort pays
    n*log2(n) passes — the kernel wins once n clears the launch and
    probe-chain overheads."""
    n, k = meta.get("n"), meta.get("k")
    if not n or not k:
        return REJECT_UNKNOWN
    e = meta.get("elem_bytes", 8)
    nv = max(meta.get("n_vals", 1), 1)
    nk = max(meta.get("n_keys", 1), 1)
    block = meta.get("block", 256)
    np_ = _pad(n, block)
    # serial slot probes (key + slot traffic, random access) + table
    # init/sort + per-column staged values through the segment kernels;
    # multi-column keys stream one extra staged i64 column each beyond
    # the packed stream already charged
    k_bytes = (np_ * (8 + 4) * SCATTER_PENALTY + 4 * k * 8 + n * nv * e
               + n * (nk - 1) * 8)
    if k <= SEGMENT_TILE_K:
        k_flops = 2.0 * np_ * k * nv  # one-hot MXU accumulation
    else:
        k_flops = float(n)  # kops serves the ref scatter instead
        k_bytes += n * nv * e * SCATTER_PENALTY
    kernel_s = _roofline_s(k_bytes, k_flops) + 2 * LAUNCH_OVERHEAD_S
    j_bytes = n * SORT_BYTES_PER_ROW * max(log2(max(n, 2)), 1.0)
    jnp_s = _roofline_s(j_bytes, n)
    return _decide(kernel_s, jnp_s,
                   f"n={n} K={k} keys={nk} vals={nv} pad={np_ - n}")


def cost_hash_probe(meta: dict) -> CostEstimate:
    """Key-sweep membership probe vs. the generic vectorized binary
    search: the kernel compares each query tile against every table key
    (n*K compares, ONCE for every output column of a fused probe), the
    jnp lowering pays log2(K) dependent random loads per row plus a
    per-column streaming pass."""
    n, k = meta.get("n"), meta.get("k")
    if not n or not k:
        return REJECT_UNKNOWN
    cols = max(meta.get("cols", 1), 1)
    e = meta.get("elem_bytes", 8)
    block = meta.get("block", 512)
    np_ = _pad(n, block)
    # one membership tile + per-column gather/compaction traffic
    k_bytes = np_ * (8 + 4 + 1 + cols * e) + k * 8
    k_flops = 1.0 * np_ * k
    kernel_s = _roofline_s(k_bytes, k_flops) + LAUNCH_OVERHEAD_S
    lgk = max(log2(max(k, 2)), 1.0)
    j_bytes = n * 8 * lgk * BSEARCH_PENALTY + n * cols * e
    jnp_s = _roofline_s(j_bytes, n * lgk)
    return _decide(kernel_s, jnp_s,
                   f"n={n} K={k} cols={cols} pad={np_ - n}")


def cost_group_build(meta: dict) -> CostEstimate:
    """CSR group build (hash-to-slot + slot histogram + payload
    ordering sort) vs. the generic sort-based groupbuilder finalize.
    Both routes order the payload rows; the kernel replaces the full
    keyed sort + segment machinery with the serial hash/histogram
    chains (random access) and a narrower ordering sort."""
    n, k = meta.get("n"), meta.get("k")
    if not n or not k:
        return REJECT_UNKNOWN
    e = meta.get("elem_bytes", 8)
    nk = max(meta.get("n_keys", 1), 1)
    block = meta.get("block", 256)
    np_ = _pad(n, block)
    lgn = max(log2(max(n, 2)), 1.0)
    # serial slot probes + histogram stores + the CSR payload ordering
    # sort + table/offsets traffic; extra staged key columns beyond the
    # packed stream cost one i64 pass each
    k_bytes = (np_ * (8 + 4) * SCATTER_PENALTY + n * 4 * SCATTER_PENALTY
               + n * 8 * lgn + 4 * k * 8 + n * (nk - 1) * 8 + n * e)
    kernel_s = _roofline_s(k_bytes, float(n)) + 2 * LAUNCH_OVERHEAD_S
    j_bytes = n * SORT_BYTES_PER_ROW * lgn
    jnp_s = _roofline_s(j_bytes, n)
    return _decide(kernel_s, jnp_s, f"n={n} K={k} keys={nk}")


def cost_group_probe(meta: dict) -> CostEstimate:
    """m:n fan-out probe: the fused membership + match-count key sweep
    vs. the generic vectorized binary search.  BOTH routes then
    pay the shared two-phase expansion (exclusive scan + repeat/gather
    into the static expansion buffer), priced by the expansion factor
    ``out``/``n`` the planner lifts off the vecbuilder size hints."""
    n, k = meta.get("n"), meta.get("k")
    if not n or not k:
        return REJECT_UNKNOWN
    out = meta.get("out") or n
    cols = max(meta.get("cols", 1), 1)
    e = meta.get("elem_bytes", 8)
    block = meta.get("block", 512)
    np_ = _pad(n, block)
    # scan + out-row binary search + per-column repeated/gathered output
    expand_bytes = n * 8.0 + out * (8 + cols * e)
    k_bytes = np_ * (8 + 4 + 1 + 4) + k * 8 + expand_bytes
    k_flops = 1.0 * np_ * k
    kernel_s = _roofline_s(k_bytes, k_flops) + LAUNCH_OVERHEAD_S
    lgk = max(log2(max(k, 2)), 1.0)
    j_bytes = n * 8 * lgk * BSEARCH_PENALTY + expand_bytes
    jnp_s = _roofline_s(j_bytes, n * lgk)
    return _decide(kernel_s, jnp_s,
                   f"n={n} K={k} cols={cols} out={out}")


def cost_matmul(meta: dict) -> CostEstimate:
    """Tiled VMEM matmul vs. XLA dot: identical arithmetic, so the gate
    is tile padding (XLA pads to 128 internally) plus launch overhead."""
    dims = meta.get("dims")
    if not dims or any(d is None for d in dims):
        return REJECT_UNKNOWN
    m, k, n = dims
    e = meta.get("elem_bytes", 8)
    bm = meta.get("bm", 256)
    bn = meta.get("bn", 256)
    bk = meta.get("bk", 512)
    mp, kp, np_ = _pad(m, bm), _pad(k, bk), _pad(n, bn)
    k_bytes = (mp * kp + kp * np_ + mp * np_) * e
    k_flops = 2.0 * mp * kp * np_
    kernel_s = _roofline_s(k_bytes, k_flops) + LAUNCH_OVERHEAD_S
    m1, k1, n1 = _pad(m, 128), _pad(k, 128), _pad(n, 128)
    j_bytes = (m1 * k1 + k1 * n1 + m1 * n1) * e
    jnp_s = _roofline_s(j_bytes, 2.0 * m1 * k1 * n1)
    return _decide(kernel_s, jnp_s, f"dims={m}x{k}x{n}")


def cost_map_chain(meta: dict) -> CostEstimate:
    """Fused elementwise chain: one guaranteed VMEM pass vs. XLA fusion
    with per-op materialization slack on deep chains."""
    n = meta.get("n")
    if not n:
        return REJECT_UNKNOWN
    cols = max(meta.get("cols", 1), 1)
    ops = meta.get("ops", 2)
    e = meta.get("elem_bytes", 8)
    block = meta.get("block", 8 * 1024)
    np_ = _pad(n, block)
    k_bytes = np_ * (cols + 1) * e
    kernel_s = _roofline_s(k_bytes, np_ * ops) + LAUNCH_OVERHEAD_S
    j_bytes = n * (cols + 1) * e * (1.0 + MAP_CHAIN_SLACK_PER_OP * min(ops, 8))
    jnp_s = _roofline_s(j_bytes, n * ops)
    return _decide(kernel_s, jnp_s, f"n={n} cols={cols} ops={ops}")


def _calibrated(spec, meta: dict, est: CostEstimate) -> CostEstimate:
    """Overlay the cost ledger's measured median over the roofline
    kernel-side estimate (see :mod:`.calibrate`).  The gate re-decides
    routing from the measured figure; ``why`` gains ``source=measured``
    vs ``source=roofline`` so ``Query.explain()`` shows which world the
    decision came from.  Best-effort: any calibration failure leaves the
    roofline estimate untouched."""
    kernel = meta.get("kernel") or getattr(spec, "name", None)
    dtype = meta.get("dtype")
    n = meta.get("n")
    hit = None
    try:
        if kernel and dtype is not None and n:
            from . import calibrate

            hit = calibrate.measured_ns(str(kernel), str(dtype), int(n))
    except Exception:
        hit = None
    if hit is None:
        if " source=" in est.why:
            return est
        return replace(est, why=f"{est.why} source=roofline")
    med_ns, calls = hit
    kernel_s = med_ns / 1e9
    routed = kernel_s <= est.jnp_s * (1.0 + ROUTE_MARGIN)
    return CostEstimate(
        kernel_s, est.jnp_s, routed,
        f"{est.why} source=measured calls={calls} "
        f"median={med_ns / 1e3:.1f}us",
        source="measured",
    )


def estimate(spec, meta: dict) -> CostEstimate:
    """Price one candidate through the spec's cost hook, then overlay
    any ledger-measured median (:func:`_calibrated`).  Specs without a
    hook route unconditionally (the pre-cost-model behavior)."""
    hook = getattr(spec, "cost", None)
    if hook is None:
        return CostEstimate(0.0, 0.0, True, "no cost hook: always route")
    est = hook(meta)
    if est is not REJECT_UNKNOWN:
        est = replace(est, why=f"{est.why} {_peaks_tag()}")
    return _calibrated(spec, meta, est)
