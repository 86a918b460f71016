"""Evaluation driver: lower → optimize → compile → execute → decode.

One `Evaluate` call == one fused XLA executable (the paper's evaluation
point).  Compiled programs are cached by alpha-invariant structure +
input signature, mirroring the paper's §7.8 observation that compile cost
amortizes across repeated evaluations.

The pipeline is split into explicit AOT stages (JaCe's
``Wrapped/Lowered/Compiled`` staging is the exemplar) so a serving tier
can hold a compiled plan and re-bind same-shape inputs without paying a
recompile:

* :func:`lower` → :class:`LoweredProgram` — inputs encoded, the
  compile-cache key formed (nothing optimized yet);
* ``LoweredProgram.optimize()`` → :class:`OptimizedProgram` — optimizer
  passes, kernel planning, autotuning, weldbound admission;
* ``OptimizedProgram.compile()`` / ``LoweredProgram.compile()`` /
  :func:`compile_program` → :class:`CompiledProgram` — the reusable AOT
  handle with ``.stats`` and ``.run(inputs)``.

The compile cache is a bounded, locked, single-flight LRU
(``$WELD_COMPILE_CACHE_MAX``, default 256): one thread compiles a given
key while peers wait on the in-flight slot, eviction is
least-recently-used, and hit/miss/evict/wait counters surface in every
result's ``stats["cache.*"]``.  ``compile_and_run`` (what `Evaluate`
calls, under the recovery ladder) drives the same stages end-to-end.
"""
from __future__ import annotations

import contextlib
import os
import re
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

# The Weld IR's i64/f64 scalars require x64; the LM stack specifies its
# dtypes explicitly everywhere so this global is benign for it.  (Pallas
# kernels trace with x64 off — see kernels/ops.py.)
jax.config.update("jax_enable_x64", True)

#: JAX's persistent compile cache.  ``$JAX_COMPILATION_CACHE_DIR`` wins
#: (JAX reads it itself); otherwise one fixed directory inside the
#: checkout — fixed, because the path is part of every entry's key.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))
if not os.environ.get(CACHE_DIR_ENV):
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from . import check  # noqa: E402
from . import faults  # noqa: E402
from . import ir  # noqa: E402
from . import obs  # noqa: E402
from . import wtypes as wt  # noqa: E402
from .analysis import bounds as _bounds  # noqa: E402
from .backend.jaxgen import emit_program  # noqa: E402
from .backend.values import WDict, WGroup, WVec  # noqa: E402
from .errors import (  # noqa: E402
    CapacityError, KernelCompileError, ResourceError, WeldError)
from .lazy import Program  # noqa: E402
from .passes import loop_count, optimize as run_passes  # noqa: E402

ENV_CACHE_MAX = "WELD_COMPILE_CACHE_MAX"
DEFAULT_CACHE_MAX = 256

#: Serializes the optimize→plan→autotune→trace compile body.  The
#: optimizer, planner, autotune cache and jax tracing all touch
#: process-global state; executions of already-compiled programs run
#: WITHOUT this lock, so concurrent serving only serializes on compiles.
_compile_lock = threading.RLock()

#: per-thread scope of :func:`measured_replays`
_replay_scope = threading.local()


def cache_max() -> int:
    """Bound on cached executables (``$WELD_COMPILE_CACHE_MAX``, ≥1)."""
    try:
        return max(1, int(os.environ.get(ENV_CACHE_MAX, DEFAULT_CACHE_MAX)))
    except ValueError:
        return DEFAULT_CACHE_MAX


class _Flight:
    """In-flight compile slot: the leader resolves it, waiters block on
    the event and take the entry from the flight itself (NOT a cache
    lookup — the entry may have been filed under a refreshed-fingerprint
    key, or already evicted under pressure)."""

    __slots__ = ("event", "entry", "error")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.entry: Optional[Tuple[object, dict]] = None
        self.error: Optional[BaseException] = None


class _CompileCache:
    """Bounded, locked, single-flight LRU of compiled executables."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, Tuple[object, dict]]" = OrderedDict()
        self._flights: Dict[str, _Flight] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.waits = 0

    def lookup_or_begin(self, key: str):
        """('hit', entry) | ('wait', flight) | ('lead', flight)."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return "hit", ent
            fl = self._flights.get(key)
            if fl is not None:
                self.waits += 1
                return "wait", fl
            fl = _Flight()
            self._flights[key] = fl
            self.misses += 1
            return "lead", fl

    def fill(self, key: str, entry: Tuple[object, dict],
             store_key: Optional[str] = None) -> None:
        """Store the compiled entry and resolve any waiters.

        The entry is stored ONLY under ``store_key`` (defaults to
        ``key``).  When first-encounter tuning refreshed the autotune
        fingerprint mid-compile, ``store_key`` is the refreshed key and
        the pre-tuning ``key`` is deliberately NOT filed: its fingerprint
        can never match a future lookup, so filing it would leak one
        forever-unreachable entry per first-encounter tuning."""
        store = store_key if store_key is not None else key
        with self._lock:
            self._entries[store] = entry
            self._entries.move_to_end(store)
            limit = cache_max()
            while len(self._entries) > limit:
                self._entries.popitem(last=False)
                self.evictions += 1
            fl = self._flights.pop(key, None)
        if fl is not None:
            fl.entry = entry
            fl.event.set()

    def abandon(self, key: str, error: BaseException) -> None:
        """Leader failed: release the flight so waiters can retry (and
        surface the same typed error if they fail the same way)."""
        with self._lock:
            fl = self._flights.pop(key, None)
        if fl is not None:
            fl.error = error
            fl.event.set()

    def clear(self) -> None:
        # in-flight compiles are left to resolve their own flights; only
        # the stored entries and the counters reset
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.evictions = self.waits = 0

    def size(self) -> int:
        with self._lock:
            return len(self._entries)

    def counters(self) -> dict:
        with self._lock:
            return {
                "cache.hits": self.hits,
                "cache.misses": self.misses,
                "cache.evictions": self.evictions,
                "cache.waits": self.waits,
                "cache.size": len(self._entries),
                "cache.max": cache_max(),
            }


_cache = _CompileCache()


def _copy_stats(v):
    """Recursively copy the stats containers (dicts/lists) while keeping
    leaf values (numbers, strings, IR exprs) by reference.  Callers get
    an isolated tree: mutating it cannot poison the cached entry."""
    if isinstance(v, dict):
        return {k: _copy_stats(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_copy_stats(x) for x in v]
    if isinstance(v, tuple):
        return tuple(_copy_stats(x) for x in v)
    return v


def clear_cache() -> None:
    _cache.clear()


def cache_size() -> int:
    return _cache.size()


def cache_stats() -> dict:
    """Global ``cache.*`` counters (also injected into every result's
    stats): hits, misses, evictions, single-flight waits, size, max."""
    return _cache.counters()


def _export_stats(stats: dict, from_cache: bool) -> dict:
    out = _copy_stats(stats)
    out.update(_cache.counters())
    out["cache.hit"] = from_cache
    return out


# ---------------------------------------------------------------------------
# staged AOT pipeline
# ---------------------------------------------------------------------------


@dataclass
class LoweredProgram:
    """Stage 1: inputs encoded, compile-cache key formed.

    ``opt``/``memory_limit``/``passes``/``mode``/``kernel_impl`` are the
    resolved compile options; ``arrays`` are the encoded (device-ready)
    inputs in ``input_names`` order — the positional binding every
    same-key execution re-binds against."""

    prog: Program
    opt: bool
    memory_limit: Optional[int]
    passes: Optional[tuple]
    mode: str
    kernel_impl: Optional[str]
    input_names: List[str] = field(default_factory=list)
    arrays: list = field(default_factory=list)
    shapes: Dict[str, tuple] = field(default_factory=dict)
    types: Dict[str, wt.WeldType] = field(default_factory=dict)
    sig: str = ""
    kreg: str = ""
    key: str = ""

    @property
    def kernelize_on(self) -> bool:
        return self.mode != "off"

    def refresh_kreg(self) -> str:
        return _kreg_fingerprint() if self.kernelize_on else ""

    def cache_key(self, kreg_now: Optional[str] = None) -> str:
        # positional input aliasing: rebuilt workflows (fresh obj ids)
        # share one compiled executable as long as structure matches.
        # Armed faults join the key too (empty when none — the common
        # path): an injected fault must never be defeated by a cached
        # executable, and a consumed fault must never serve the poisoned
        # executable it produced
        name_map = {n: f"in{i}" for i, n in enumerate(self.input_names)}
        kreg_now = self.kreg if kreg_now is None else kreg_now
        return (
            ir.canon_key(self.prog.expr, name_map)
            + f"|opt={self.opt}|mem={self.memory_limit}|passes={self.passes}"
            + f"|kz={self.mode}|kimpl={self.kernel_impl}|kreg={kreg_now}"
            + f"|flt={faults.fingerprint()}|{self.sig}"
        )

    def optimize(self) -> "OptimizedProgram":
        """Stage 2: optimizer passes + kernel planning + autotuning +
        weldbound admission.  Uncached — callers wanting the shared
        cache go through :meth:`compile` / :func:`compile_program`."""
        with _compile_lock:
            return _optimize_stage(self)

    def compile(self) -> "CompiledProgram":
        """Stages 2+3 through the shared single-flight cache."""
        jitted, stats, from_cache = _compile_handle(self)
        return CompiledProgram(self, jitted, stats, from_cache)


def _kreg_fingerprint() -> str:
    from .kernelplan import autotune, fingerprint, quarantine

    return (fingerprint() + "/" + autotune.fingerprint()
            + "/" + quarantine.fingerprint())


def lower(
    prog: Program,
    optimize: bool = True,
    memory_limit: Optional[int] = None,
    passes=None,
    kernelize=None,
    kernel_impl: Optional[str] = None,
) -> LoweredProgram:
    """Public stage-1 entry: resolve options, encode inputs, form the key."""
    from .kernelplan import normalize_kernelize

    mode = normalize_kernelize(kernelize)
    if mode != "off" and kernel_impl is None:
        # resolve the kernel library's default NOW so it lands in the
        # compile-cache key — kops promises set_default_impl() always
        # takes effect, which a cached executable would otherwise defeat
        from ..kernels import ops as _kops

        kernel_impl = _kops.default_impl()
    return _lower(prog, optimize, memory_limit, passes, mode, kernel_impl)


def _lower(prog, optimize, memory_limit, passes, mode,
           kernel_impl) -> LoweredProgram:
    low = LoweredProgram(prog=prog, opt=optimize, memory_limit=memory_limit,
                         passes=passes, mode=mode, kernel_impl=kernel_impl)
    low.input_names = sorted(prog.inputs)
    with _encode_span(len(low.input_names)) as sp:
        for name in low.input_names:
            ty, enc, data = prog.inputs[name]
            arr = _bind(enc, data, prog.resident.get(name), sp)
            low.arrays.append(arr)
            low.shapes[name] = tuple(arr.shape)
            low.types[name] = ty
    low.sig = ",".join(f"{a.dtype}:{a.shape}" for a in low.arrays)
    if low.kernelize_on:
        # register/unregister, new tunings AND quarantine changes must
        # invalidate the cache: a stale executable must never serve a
        # newly tuned plan or a newly quarantined kernel route
        low.kreg = _kreg_fingerprint()
    low.key = low.cache_key()
    return low


def _encode_span(n_inputs: int):
    """The ``encode`` span of one binding of ``n_inputs`` inputs.  Its
    counters start at 0: ``bytes`` counts the bytes uploaded, ``resident``
    the inputs bound from a filled :class:`~repro.core.lazy.DeviceSlot`."""
    sp = obs.span("encode", inputs=n_inputs)
    sp.count("bytes", 0)
    sp.count("resident", 0)
    return sp


def _to_device(host, encode_span):
    """``jnp.asarray`` of one input, which queues its upload and returns;
    the bytes of a host input count on the ``encode`` span."""
    arr = jnp.asarray(host)
    if obs.enabled() and not isinstance(host, jax.Array):
        encode_span.count("bytes", arr.nbytes)
    return arr


def _bind(enc, data, slot, encode_span):
    """One input on the device.  Without a slot it is encoded and
    uploaded at every bind.  A slot is filled by the first bind, one
    thread at a time, and every later bind takes its buffer."""
    if slot is None:
        return _to_device(enc.encode(data), encode_span)
    with slot.lock:
        if slot.value is None:
            slot.value = _to_device(enc.encode(data), encode_span)
            return slot.value
    encode_span.count("resident")
    return slot.value


@dataclass
class OptimizedProgram:
    """Stage 2 result: the planned IR + stats, ready to jit."""

    lowered: LoweredProgram
    expr: ir.Expr
    stats: dict
    optimize_ms: float = 0.0

    def compile(self) -> "CompiledProgram":
        """Stage 3: emit + jit + AOT-compile, then file the executable in
        the shared cache (under the refreshed autotune-fingerprint key
        when first-encounter tuning bumped it — the stale pre-tuning key
        is never stored, so it cannot leak)."""
        low = self.lowered
        with _compile_lock:
            jitted = _jit_stage(low, self.expr, self.stats,
                                self.optimize_ms)
        store_key = low.key
        if low.kernelize_on:
            kreg_now = low.refresh_kreg()
            if kreg_now != low.kreg:
                store_key = low.cache_key(kreg_now)
        _cache.fill(low.key, (jitted, self.stats), store_key=store_key)
        return CompiledProgram(low, jitted, self.stats, from_cache=False)


def _optimize_stage(low: LoweredProgram) -> OptimizedProgram:
    t0 = time.perf_counter()
    expr = low.prog.expr
    stats: dict = {}
    stats["loops.before"] = loop_count(expr)
    # verify the frontend's program before any rewrite touches it: a
    # pre-existing violation must be blamed on the input, not on
    # whichever pass happens to run first
    check.checkpoint("input", expr, env=low.types, stats=stats,
                     shapes=low.shapes)
    if low.opt:
        with obs.span("optimize") as sp:
            expr = run_passes(expr, passes=low.passes, stats=stats,
                              input_shapes=low.shapes)
            sp.set("iterations", stats.get("iterations"))
    stats["loops.after"] = loop_count(expr)
    if low.kernelize_on:
        from .kernelplan import autotune, plan_kernels

        with obs.span("kernelplan", mode=low.mode) as sp:
            expr = plan_kernels(expr, input_shapes=low.shapes, stats=stats,
                                mode=low.mode, impl=low.kernel_impl)
            sp.set("matched", stats.get("kernelize.matched", 0))
            for c in ("dense_group.keys", "dense_group.words",
                      "frontpack.cols"):
                if c in stats["kernelplan"]:
                    sp.count(c, stats["kernelplan"][c])
        if stats.get("kernelize.matched"):
            with obs.span("autotune"):
                expr = autotune.tune_plan(expr, impl=low.kernel_impl,
                                          stats=stats)
            check.checkpoint("autotune", expr, stats=stats,
                             shapes=low.shapes)
    # the planned IR is part of the stats so explain()/the measured
    # replay can reach the program that actually ran (cache hits
    # included — the expr rides along in the cached stats entry).
    # plan.inputs pins the COMPILE-time input binding: a later hit
    # from a rebuilt workflow has fresh obj ids, but its arrays map
    # positionally onto these names (the cache key aliases inputs
    # positionally), so the replay re-binds them the same way
    stats["plan.ir"] = expr
    stats["plan.inputs"] = (list(low.input_names), dict(low.types),
                            dict(low.shapes))
    _admit(low, expr, stats)
    return OptimizedProgram(lowered=low, expr=expr, stats=stats,
                            optimize_ms=(time.perf_counter() - t0) * 1e3)


def _admit(low: LoweredProgram, expr: ir.Expr, stats: dict) -> None:
    """Weldbound admission: evaluate the plan's symbolic peak-memory
    certificate against the bound inputs and reject BEFORE tracing — a
    rejected plan costs zero kernel launches and is never cached.
    Analysis OR certificate-evaluation failures only disable admission
    (the emitter's own trace-time charging still guards execution)."""
    if not _bounds.enabled():
        return
    tb0 = time.perf_counter()
    admitted = True
    brep = None
    with obs.span("bounds") as sp:
        try:
            brep = _bounds.analyze(expr)
        except Exception:
            brep = None
        if brep is not None:
            try:
                peak = brep.peak(low.shapes)
                certificate = brep.certificate()
                builders = brep.builder_lines(low.shapes)
                out_rows = brep.result_rows(low.shapes)
            except Exception as e:
                # the certificate itself failed to evaluate at these
                # shapes — same contract as an analysis failure: degrade
                # to trace-time charging, never kill the compile
                brep = None
                stats.pop("bounds.certificate", None)
                stats["bounds.degraded"] = f"{type(e).__name__}: {e}"
                sp.set("degraded", stats["bounds.degraded"])
        if brep is not None:
            admitted = (low.memory_limit is None
                        or peak <= int(low.memory_limit))
            stats["bounds.certificate"] = certificate
            stats["bounds.peak_bytes"] = peak
            stats["bounds.builders"] = builders
            stats["bounds.out_rows"] = out_rows
            stats["bounds.admitted"] = admitted
            sp.set("peak_bytes", peak)
            sp.set("admitted", admitted)
    stats["bounds.ms"] = round((time.perf_counter() - tb0) * 1e3, 3)
    if brep is not None and not admitted:
        raise ResourceError(
            f"plan rejected at admission: peak-memory certificate "
            f"{stats['bounds.certificate']} = "
            f"{stats['bounds.peak_bytes']} bytes exceeds "
            f"memory_limit={int(low.memory_limit)} (builder size "
            f"hints + kernel scratch footprints provably do not "
            f"fit; nothing was traced or launched)")


def _jit_stage(low: LoweredProgram, expr: ir.Expr, stats: dict,
               optimize_ms: float) -> object:
    t0 = time.perf_counter()
    with obs.span("jit_compile"):
        fn = emit_program(expr, low.input_names, low.types, low.shapes,
                          low.memory_limit, kernel_impl=low.kernel_impl)
        jitted = jax.jit(fn)
        # trigger tracing+compilation now so compile_ms is honest
        try:
            _ = jitted.lower(*low.arrays).compile()
        except WeldError:
            raise
        except Exception as e:
            if low.kernelize_on and _MOSAIC_RE.search(str(e)):
                raise _kernel_compile_error(e, stats, low.kernel_impl) from e
            raise
    stats["compile_ms"] = optimize_ms + (time.perf_counter() - t0) * 1e3
    return jitted


#: how the TPU kernel compiler (or Pallas' lowering into it) names itself
#: in the errors it raises at ``jit(...).lower()`` / ``.compile()``.
_MOSAIC_RE = re.compile(r"Mosaic|Pallas|pallas_call")


def _kernel_compile_error(e: Exception, stats: dict,
                          impl: Optional[str]) -> KernelCompileError:
    """Type a kernel compiler failure of the whole program: it names
    the offending kernel when the plan routed exactly one."""
    routed = sorted(stats.get("kernelplan", {}).get("routed", {}))
    return KernelCompileError(
        f"kernel compile failed (impl={impl}, routed kernels: "
        f"{', '.join(routed) or 'none'}): {type(e).__name__}: {e}",
        kernel=routed[0] if len(routed) == 1 else None, impl=impl)


def _compile_handle(low: LoweredProgram) -> Tuple[object, dict, bool]:
    """The cached, single-flight compile driver: one thread compiles a
    key, peers wait on the flight and receive the entry from it."""
    while True:
        with obs.span("cache.lookup") as sp:
            kind, payload = _cache.lookup_or_begin(low.key)
            sp.set("hit", kind == "hit")
        if kind == "hit":
            jitted, stats = payload
            return jitted, stats, True
        if kind == "wait":
            with obs.span("cache.wait"):
                payload.event.wait()
            if payload.entry is not None:
                jitted, stats = payload.entry
                return jitted, stats, True
            # leader failed: loop — this thread may become the next
            # leader and surface the same typed error itself
            continue
        try:
            opt = low.optimize()
            handle = opt.compile()  # fills the cache + resolves the flight
        except BaseException as e:
            _cache.abandon(low.key, e)
            raise
        return handle._jitted, handle._cached_stats, False


class CompiledProgram:
    """Stage-3 AOT handle: one compiled (plan, shape-signature)
    executable plus its compile-time stats.  ``run()`` re-binds
    same-shape inputs with zero recompiles; data-dependent capacity
    poison at decode still climbs the full recovery ladder."""

    def __init__(self, lowered: LoweredProgram, jitted, stats: dict,
                 from_cache: bool) -> None:
        self._low = lowered
        self._jitted = jitted
        self._cached_stats = stats
        self.from_cache = from_cache

    @property
    def key(self) -> str:
        return self._low.key

    @property
    def out_ty(self) -> wt.WeldType:
        return self._low.prog.out_ty

    @property
    def stats(self) -> dict:
        return _export_stats(self._cached_stats, self.from_cache)

    def signature(self) -> str:
        """dtype:shape signature the executable was compiled against."""
        return self._low.sig

    def run(self, inputs=None, *, recover: bool = True):
        """Execute and decode the result: against the inputs the handle
        was lowered with, or with ``inputs`` bound anew.  ``inputs`` maps
        an input name to its native value and its device slot (None for
        a value without one), each bound as :func:`lower` binds it; the
        inputs it leaves out keep the handle's own.

        Same shapes+dtypes are the caller's contract (checked against
        the compiled signature).  On capacity poison — re-bound data
        overflowing the plan's baked builder capacities — the full
        recovery ladder re-runs the program with regrown capacities."""
        low = self._low
        arrays = low.arrays
        if inputs is not None:
            arrays = list(arrays)
            pos = {name: i for i, name in enumerate(low.input_names)}
            with _encode_span(len(inputs)) as sp:
                for name, (data, slot) in inputs.items():
                    arrays[pos[name]] = _bind(low.prog.inputs[name][1],
                                              data, slot, sp)
            sig = ",".join(f"{a.dtype}:{a.shape}" for a in arrays)
            if sig != low.sig:
                raise ValueError(
                    f"CompiledProgram.run: bound inputs {sig} do not "
                    f"match the compiled signature {low.sig}; re-lower "
                    "and compile for new shapes/dtypes")
        with obs.span("weld.run", from_cache=self.from_cache):
            try:
                return _execute(low, self._jitted, self._cached_stats,
                                arrays)
            except CapacityError:
                from . import recovery

                if not recover or not recovery.enabled():
                    raise
        # capacity poison under recovery: rebuild a Program bound to
        # THESE arrays and climb the full ladder (regrow → fallback)
        prog2 = Program(
            expr=low.prog.expr,
            inputs={name: (low.types[name], low.prog.inputs[name][1],
                           arrays[i])
                    for i, name in enumerate(low.input_names)},
            out_ty=low.prog.out_ty,
        )
        value, _, _, _ = compile_and_run(
            prog2, optimize=low.opt, memory_limit=low.memory_limit,
            passes=low.passes, kernelize=low.mode,
            kernel_impl=low.kernel_impl)
        return value


def compile_program(
    prog: Program,
    optimize: bool = True,
    memory_limit: Optional[int] = None,
    passes=None,
    kernelize=None,
    kernel_impl: Optional[str] = None,
) -> CompiledProgram:
    """AOT entry: lower → (cached, single-flight) optimize + compile.
    Nothing is executed; the returned handle's ``run()`` re-binds
    same-shape inputs against the cached executable."""
    low = lower(prog, optimize=optimize, memory_limit=memory_limit,
                passes=passes, kernelize=kernelize, kernel_impl=kernel_impl)
    with obs.span("weld.compile", kernelize=low.mode,
                  impl=low.kernel_impl) as sp:
        jitted, stats, from_cache = _compile_handle(low)
        sp.set("from_cache", from_cache)
    return CompiledProgram(low, jitted, stats, from_cache)


# ---------------------------------------------------------------------------
# end-to-end driver (Evaluate path)
# ---------------------------------------------------------------------------


def compile_and_run(
    prog: Program,
    optimize: bool = True,
    memory_limit: Optional[int] = None,
    passes=None,
    kernelize=None,
    kernel_impl: Optional[str] = None,
):
    """Returns (value, compile_ms, from_cache, stats).

    ``kernelize`` selects the kernel-planner mode — ``"auto"`` (the
    process default: roofline-cost-gated routing), ``"always"``
    (``True``: route every match), or ``"off"`` (``False``).  The
    planner runs after optimization so matched loops dispatch to the
    Pallas kernel library; the block-size autotuner then bakes tuned
    tile parameters into the plan.  ``kernel_impl`` selects
    ref / interpret / pallas for those calls (None = the kernel
    library's own default).
    """
    # kernelplan (and the Pallas kernel library behind it) is imported
    # lazily so kernelize="off" evaluations never pay its import cost
    from .kernelplan import normalize_kernelize
    from .recovery import run_with_recovery

    mode = normalize_kernelize(kernelize)
    kernelize_on = mode != "off"
    if kernelize_on and kernel_impl is None:
        from ..kernels import ops as _kops

        kernel_impl = _kops.default_impl()
    with obs.span("weld.evaluate", kernelize=mode, impl=kernel_impl) as root:
        # the recovery ladder owns retries: capacity poison regrows
        # builder capacities then degrades to the generic lowering;
        # kernel stage/compile failures quarantine the offender and
        # degrade immediately (see core/recovery.py)
        return run_with_recovery(
            _compile_and_run, prog, optimize=optimize,
            memory_limit=memory_limit, passes=passes, mode=mode,
            kernel_impl=kernel_impl, root=root,
        )


def _compile_and_run(prog, optimize, memory_limit, passes, mode,
                     kernelize_on, kernel_impl, root):
    del kernelize_on  # carried by mode
    low = _lower(prog, optimize, memory_limit, passes, mode, kernel_impl)
    jitted, stats, from_cache = _compile_handle(low)
    compile_ms = 0.0 if from_cache else stats.get("compile_ms", 0.0)
    root.set("from_cache", from_cache)
    value = _execute(low, jitted, stats, low.arrays)
    return value, compile_ms, from_cache, _export_stats(stats, from_cache)


def _execute(low: LoweredProgram, jitted, stats: dict, arrays):
    """Launch, wait for the result, decode: the one execution path of
    ``CompiledProgram.run`` and of ``compile_and_run``.

    The launch is queued at once, behind the inputs' upload.  While
    tracing, ``upload`` then waits for the inputs to land, so that
    ``execute`` times the device's queue and run alone; untraced,
    nothing waits on the inputs.  Inside :func:`measured_replays` a
    kernelized plan also runs its measured replay."""
    with obs.span("upload"):
        out = jitted(*arrays)
        if obs.enabled():
            jax.block_until_ready(arrays)
    with obs.span("execute"):
        out = jax.block_until_ready(out)
    if (getattr(_replay_scope, "on", False)
            and stats.get("kernelize.matched")
            and stats.get("plan.ir") is not None
            and stats.get("plan.inputs") is not None):
        pnames, ptypes, pshapes = stats["plan.inputs"]
        _measured_replay(stats["plan.ir"], pnames, ptypes, pshapes,
                         low.memory_limit, low.kernel_impl, arrays)
    with obs.span("decode"):
        faults.maybe_raise("decode")
        if faults.poisoned("decode"):
            raise CapacityError("fault injected at decode: result poisoned")
        # every device buffer of the result comes to the host here, all
        # copies issued at once; decode_value then only slices host arrays
        with obs.span("fetch") as sp:
            if obs.enabled():
                sp.count("bytes", sum(
                    x.nbytes for x in jax.tree_util.tree_leaves(out)
                    if isinstance(x, jax.Array)))
            host = jax.device_get(out)
        return decode_value(host, low.prog.out_ty)


@contextlib.contextmanager
def measured_replays():
    """On this thread, for the block: every kernelized execution also
    runs :func:`_measured_replay`.  ``Query.explain(analyze=True)`` is
    the scope's one user; served and evaluated queries never replay."""
    prev = getattr(_replay_scope, "on", False)
    _replay_scope.on = True
    try:
        yield
    finally:
        _replay_scope.on = prev


def _measured_replay(expr, input_names, types, shapes, memory_limit,
                     kernel_impl, arrays) -> None:
    """Re-run the planned program eagerly (unjitted) with per-kernel
    timing enabled, so each ``KernelCall`` gets its own measured span and
    a cost-ledger record.  The fused jitted executable gives no per-call
    boundaries, so EXPLAIN ANALYZE pays one extra eager pass to get
    per-kernel wall times (adapter overhead included — the same thing
    the roofline model prices).  Best-effort: a replay failure is
    recorded on the span, never raised.  Serialized under the compile
    lock: the eager pass runs through the same global emitter state a
    concurrent compile would be mutating."""
    with obs.span("measure.replay") as sp:
        try:
            faults.maybe_raise("measure.replay")
            with _compile_lock:
                fn = emit_program(expr, input_names, types, shapes,
                                  memory_limit, kernel_impl=kernel_impl,
                                  measure=True)
                out = fn(*arrays)
            jax.block_until_ready(out)
        except Exception as e:  # pragma: no cover - defensive
            sp.set("error", f"{type(e).__name__}: {e}")


def decode_value(v, ty: wt.WeldType):
    """Backend value -> natural host value (numpy arrays / dicts / tuples)."""
    if isinstance(v, WVec):
        data = v.to_numpy()
        return data
    if isinstance(v, WDict):
        return v.to_numpy()
    if isinstance(v, WGroup):
        return v.to_numpy()
    if isinstance(v, tuple):
        if isinstance(ty, wt.Struct):
            return tuple(
                decode_value(x, f) for x, f in zip(v, ty.fields)
            )
        return tuple(decode_value(x, None) for x in v)
    if hasattr(v, "shape") and getattr(v, "shape", None) == ():
        return np.asarray(v).item()
    return np.asarray(v)
