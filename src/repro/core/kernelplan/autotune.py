"""Empirical block-size autotuner for planned kernel calls.

The hand-picked ``BLOCK`` constants in ``repro.kernels.*`` are good
defaults for one tile regime; the best block is a function of dtype,
problem size, and platform.  On the *first encounter* of a
``(kernel, dtype, size-bucket, impl)`` key the tuner times the spec's
``tune_space`` grid on a synthetic workload of the same shape
(``spec.make_bench``), memoizes the winner in an on-disk JSON cache,
and every later compile reuses it for free.

The cache lives next to nothing volatile — default
``~/.cache/weld-repro/autotune.json``, overridable via
``$WELD_AUTOTUNE_CACHE`` — and its :func:`fingerprint` participates in
the runtime's compile-cache key, so a newly tuned plan can never be
served by a stale executable (the key changes, the program recompiles
with the tuned blocks baked in).

Timing only happens for real kernel paths (``impl`` "pallas" /
"interpret"); the pure-jnp ``"ref"`` oracle ignores block sizes, so the
tuner short-circuits to the module defaults without touching the cache.
Sizes are bucketed to the next power of two: one tuning run serves
every problem in the bucket.

``tune_plan`` is the planner-side entry: it walks a planned program and
bakes the chosen parameters into each ``KernelCall``'s static params
(where the registry adapters forward them to ``repro.kernels.ops`` and
``pretty.py`` displays them).
"""
from __future__ import annotations

import json
import os
import time
import warnings
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

from .. import ir
from .. import obs
from .. import wtypes as wt
from . import registry as reg

ENV_CACHE = "WELD_AUTOTUNE_CACHE"
ENV_DISABLE = "WELD_AUTOTUNE_DISABLE"

#: timing schedule per candidate: warmup (compile) + timed reps (min).
WARMUP = 1
REPS = 3

#: floor bucket so micro sizes don't fragment the cache.
MIN_BUCKET = 1024

_cache: Optional[Dict[str, dict]] = None  # lazily loaded from disk
_generation = 0  # bumps on every mutation (part of fingerprint)


def cache_path() -> str:
    return os.environ.get(ENV_CACHE) or os.path.join(
        os.path.expanduser("~"), ".cache", "weld-repro", "autotune.json"
    )


def _load() -> Dict[str, dict]:
    global _cache
    if _cache is None:
        path = cache_path()
        try:
            with open(path) as f:
                _cache = json.load(f)
            if not isinstance(_cache, dict):
                raise ValueError("cache root is not an object")
        except OSError:
            _cache = {}  # no cache yet: normal first run
        except ValueError as e:
            # corrupt/truncated JSON (e.g. a crashed writer before the
            # save became atomic) must not break the compile — start
            # empty and re-tune; the next _save overwrites the bad file.
            # Name the file and the parse error so the user can inspect
            # or delete it instead of silently re-tuning forever.
            warnings.warn(
                f"autotune cache {path} is corrupt ({e}); ignoring it "
                "and re-tuning from scratch — delete the file to silence "
                "this warning",
                RuntimeWarning, stacklevel=2,
            )
            _cache = {}
    return _cache


def _save() -> None:
    from .. import faults

    path = cache_path()
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        # the io.autotune_cache failpoint proves persistence really is
        # best-effort: an injected OSError must leave tuning in-process
        faults.maybe_raise("io.autotune_cache", exc=OSError)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(_cache, f, indent=1, sort_keys=True)
        os.replace(tmp, path)  # atomic: readers never see a partial file
    except OSError:
        try:
            os.remove(tmp)
        except OSError:
            pass
        # tuning still applies in-process; persistence is best-effort


def clear_cache(disk: bool = True) -> None:
    """Reset tunings (tests / after a platform change)."""
    global _cache, _generation
    _cache = {}
    _generation += 1
    if disk:
        try:
            os.remove(cache_path())
        except OSError:
            pass


def invalidate(kernel: Optional[str] = None) -> int:
    """Drop cached tunings for one kernel (or all); returns drop count."""
    global _generation
    c = _load()
    keys = [k for k in c if kernel is None or k.startswith(f"{kernel}|")]
    for k in keys:
        del c[k]
    if keys:
        _generation += 1
        _save()
    return len(keys)


def fingerprint() -> str:
    """Stable digest of the tuning state for the compile-cache key."""
    import zlib

    c = _load()
    items = ";".join(
        f"{k}={sorted(v.get('params', {}).items())}" for k, v in sorted(c.items())
    )
    return f"g{_generation}n{len(c)}h{zlib.crc32(items.encode()):08x}"


def size_bucket(n: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b <<= 1
    return b


def _key(kernel: str, dtype, n: int, impl: str,
         k: Optional[int] = None, dims: Optional[tuple] = None) -> str:
    """Cache key.  K (segment width) and matmul dims shape the kernels'
    tile trade-offs as much as n does, so they are part of the key —
    a block tuned for K=256 must not be served to a K=4096 call."""
    extra = f"|k{size_bucket(int(k))}" if k else ""
    if dims:
        extra += "|d" + "x".join(str(size_bucket(int(d))) for d in dims)
    return f"{kernel}|{np.dtype(dtype).name}|{size_bucket(int(n))}{extra}|{impl}"


def _grid(space: Dict[str, tuple]) -> Iterable[Dict[str, int]]:
    names = sorted(space)
    points = [{}]
    for name in names:
        points = [dict(p, **{name: v}) for p in points for v in space[name]]
    return points


def _time_candidate(go) -> float:
    for _ in range(WARMUP):
        go()
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        go()
        best = min(best, time.perf_counter() - t0)
    return best


def lookup(kernel: str, dtype, n: int, impl: str,
           k: Optional[int] = None,
           dims: Optional[tuple] = None) -> Optional[Dict[str, int]]:
    ent = _load().get(_key(kernel, dtype, n, impl, k=k, dims=dims))
    return dict(ent["params"]) if ent else None


def tune(spec: "reg.KernelSpec", meta: dict, impl: str,
         force: bool = False) -> Tuple[Dict[str, int], bool]:
    """Resolve tuned params for one call site.

    Returns ``(params, from_cache)``.  Falls back to the spec's defaults
    (without timing or cache writes) when tuning cannot help: no tunable
    space, no bench, unknown size, the ref oracle, or tuning disabled.
    """
    global _generation
    n = meta.get("n") or 0
    k, dims = meta.get("k"), meta.get("dims")
    defaults = dict(spec.tune_defaults)
    if not spec.tune_space or n <= 0:
        return defaults, False
    if impl in (None, "ref") or os.environ.get(ENV_DISABLE):
        return defaults, False
    cached = None if force else lookup(spec.name, meta.get("dtype", "f8"),
                                       n, impl, k=k, dims=dims)
    if cached is not None:
        return cached, True
    if spec.make_bench is None:
        return defaults, False
    # time the grid on a synthetic same-bucket workload
    bench_meta = dict(meta, n=size_bucket(n))
    best_params, best_t = defaults, float("inf")
    failures = []
    with obs.span("autotune.tune", kernel=spec.name, n=size_bucket(n),
                  impl=impl) as tsp:
        for cand in _grid(spec.tune_space):
            try:
                from .. import faults

                faults.maybe_raise("autotune.time")
                go = spec.make_bench(bench_meta, cand, impl)
                t = _time_candidate(go)
            except Exception as e:
                failures.append(f"{cand}: {type(e).__name__}: {e}")
                continue  # candidate invalid for this shape — skip
            if t < best_t:
                best_params, best_t = cand, t
        tsp.set("best", dict(best_params))
        if best_t < float("inf"):
            tsp.set("us", round(best_t * 1e6, 2))
    if best_t == float("inf") and impl == "pallas":
        # no candidate compiled on the device: caching the defaults would
        # only hide the failure until the plan launches them
        from ..errors import KernelCompileError

        raise KernelCompileError(
            f"autotune: every {spec.name} candidate failed on pallas; "
            + " | ".join(failures[:3]),
            kernel=spec.name, impl=impl, dtype=str(np.dtype(
                meta.get("dtype", "f8"))), n=n)
    c = _load()
    c[_key(spec.name, meta.get("dtype", "f8"), n, impl, k=k, dims=dims)] = {
        "params": best_params,
        "us": round(best_t * 1e6, 2) if best_t < float("inf") else None,
    }
    _generation += 1
    _save()
    return dict(best_params), False


# ---------------------------------------------------------------------------
# Plan-level entry: bake tuned params into KernelCall nodes
# ---------------------------------------------------------------------------


def _np_dtype_of(ty: wt.WeldType):
    if isinstance(ty, wt.Vec):
        return _np_dtype_of(ty.elem)
    if isinstance(ty, wt.Struct):
        return _np_dtype_of(ty.fields[0]) if ty.fields else np.float64
    if isinstance(ty, wt.DictType):
        return _np_dtype_of(ty.val)
    if isinstance(ty, wt.Scalar):
        return np.dtype(ty.np_dtype)
    return np.float64


def tune_plan(e: ir.Expr, impl: Optional[str],
              stats: Optional[dict] = None) -> ir.Expr:
    """Attach tuned (or default) block parameters to every planned
    ``KernelCall``.  Identity when the program has no kernel calls."""
    events = []

    def rec(x: ir.Expr) -> ir.Expr:
        x = x.map_children(rec)
        if not isinstance(x, ir.KernelCall):
            return x
        spec = reg.available(x.kernel)
        if spec is None or not spec.tune_space:
            return x
        params = dict(x.params)
        if any(k in params for k in spec.tune_space):
            return x  # already tuned (e.g. plan reuse)
        meta = {
            "kernel": x.kernel,
            "n": params.get("n_rows") if params.get("n_rows", -1) > 0 else None,
            "k": params.get("capacity") or params.get("k"),
            "dims": params.get("dims"),
            "dtype": _np_dtype_of(x.ret_ty),
        }
        chosen, from_cache = tune(spec, meta, impl)
        if not chosen:
            return x
        events.append({
            "kernel": x.kernel,
            "n": meta["n"],
            "params": dict(chosen),
            "cached": from_cache,
        })
        return ir.KernelCall(
            kernel=x.kernel,
            args=x.args,
            ret_ty=x.ret_ty,
            params=x.params + tuple(sorted(chosen.items())),
            fns=x.fns,
        )

    out = rec(e)
    if stats is not None and events:
        stats.setdefault("kernelplan", {})["autotune"] = events
    return out
