"""Adaptive recovery runtime: typed errors, fault injection, the
poison-triggered retry ladder, and kernel quarantine.

Every test arms deterministic failpoints (``repro.faults``) to reach
degradation paths that are unreachable on healthy inputs, then asserts
the documented contract: results stay oracle-correct, every step is
observable (RuntimeWarning + ``recovery.*`` stats + weldtrace spans),
and with recovery disabled the typed exception surfaces instead.
"""
import warnings

import numpy as np
import pytest

from repro.core import faults, recovery, runtime
from repro.core.errors import (
    CapacityError, InjectedFault, KernelCompileError, ResourceError,
    WeldError,
)
from repro.core.kernelplan import quarantine
from repro.frames.weldrel import Query, Table

rng = np.random.RandomState(7)


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    """Every test gets a private health file, disarmed faults, a cold
    compile cache, and tmp-dir autotune/ledger artifacts."""
    monkeypatch.setenv(quarantine.ENV_FILE,
                       str(tmp_path / "kernel_health.json"))
    monkeypatch.setenv("WELD_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setenv("WELD_COST_LEDGER", str(tmp_path / "ledger.jsonl"))
    quarantine.clear(disk=False)
    faults.clear()
    runtime.clear_cache()
    yield
    faults.clear()
    quarantine.clear(disk=False)
    runtime.clear_cache()


# ---------------------------------------------------------------------------
# typed exception hierarchy (satellite: repro.errors)
# ---------------------------------------------------------------------------


def test_error_hierarchy_contracts():
    import repro.errors as top

    assert issubclass(WeldError, RuntimeError)
    # CapacityError must satisfy BOTH historical catch sites: decode
    # poison raised RuntimeError, the join capacity guard ValueError
    assert issubclass(CapacityError, WeldError)
    assert issubclass(CapacityError, ValueError)
    assert issubclass(ResourceError, WeldError)
    assert issubclass(KernelCompileError, WeldError)
    assert issubclass(InjectedFault, WeldError)
    for name in ("WeldError", "CapacityError", "ResourceError",
                 "KernelCompileError", "InjectedFault"):
        assert getattr(top, name) is globals()[name]
    e = KernelCompileError("boom", kernel="hash_probe", impl="pallas",
                           dtype="f8", n=4096)
    assert (e.kernel, e.impl, e.dtype, e.n) == ("hash_probe", "pallas",
                                                "f8", 4096)


def test_jaxgen_memory_error_is_resource_error():
    from repro.core.backend.jaxgen import WeldMemoryError

    assert WeldMemoryError is ResourceError


# ---------------------------------------------------------------------------
# fault-injection mechanics (satellite: repro.faults)
# ---------------------------------------------------------------------------


def test_fault_spec_env_parsing(monkeypatch):
    import repro.faults as top

    assert top.inject is faults.inject
    monkeypatch.setenv(faults.ENV_FAULTS,
                       "kernel.hash_probe:raise@2, dict.build:poison,"
                       "join.capacity:cap=7@3")
    monkeypatch.setattr(faults, "_armed", None)  # force env re-read
    armed = faults.armed()
    assert armed["kernel.hash_probe"][0] == {
        "action": "raise", "value": None, "remaining": 2}
    assert armed["dict.build"][0]["remaining"] == 1
    assert armed["join.capacity"][0] == {
        "action": "cap", "value": 7, "remaining": 3}
    monkeypatch.setattr(faults, "_armed", None)
    monkeypatch.setenv(faults.ENV_FAULTS, "garbage-no-colon")
    with pytest.raises(ValueError, match="site:action"):
        faults.armed()
    monkeypatch.setattr(faults, "_armed", None)
    monkeypatch.setenv(faults.ENV_FAULTS, "x:frobnicate")
    with pytest.raises(ValueError, match="unknown fault action"):
        faults.armed()
    monkeypatch.setattr(faults, "_armed", None)
    monkeypatch.delenv(faults.ENV_FAULTS)


def test_fault_consumption_and_fingerprint():
    assert faults.fingerprint() == ""  # unarmed: no cache-key pollution
    faults.inject("decode", "raise", times=2)
    fp0 = faults.fingerprint()
    assert "decode:raise@2" in fp0
    with pytest.raises(InjectedFault, match="fault injected at decode"):
        faults.maybe_raise("decode")
    assert faults.fingerprint() != fp0  # remaining count is in the key
    faults.maybe_raise("io.test-site")  # unarmed site: no-op
    with pytest.raises(InjectedFault):
        faults.maybe_raise("decode")
    faults.maybe_raise("decode")  # spent: no-op
    assert faults.fingerprint() == ""
    assert [f["site"] for f in faults.fired()] == ["decode", "decode"]
    # exc= substitutes the class at best-effort IO sites
    faults.inject("io.ledger", "raise")
    with pytest.raises(OSError):
        faults.maybe_raise("io.ledger", exc=OSError)


# ---------------------------------------------------------------------------
# the recovery ladder
# ---------------------------------------------------------------------------


def _join_tables():
    L = Table({"k": np.array([1, 2, 2, 3, 3, 3], dtype=np.int64),
               "a": np.array([10.0, 20, 21, 30, 31, 32])})
    R = Table({"k": np.array([2, 2, 3, 5], dtype=np.int64),
               "b": np.array([1.0, 2, 3, 4])})
    return L, R


def _rowset(t):
    cols = sorted(t.cols)
    arrs = [np.asarray(t.cols[c].to_numpy()) for c in cols]
    return {tuple(str(a[i]) for a in arrs) for i in range(len(arrs[0]))}


def test_mn_join_capacity_fault_recovers_to_oracle():
    """An injected undersized build capacity poisons the m:n group
    build; the ladder regrows it and the final rows match the
    un-faulted run (the pandas-oracle shape, see test_join_fuzz)."""
    L, R = _join_tables()
    want = _rowset(Query(L).join(R, on="k", kernelize="always"))
    runtime.clear_cache()
    faults.inject("join.capacity", "cap", times=1, value=1)
    st: dict = {}
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = Query(L).join(R, on="k", kernelize="always", collect_stats=st)
    assert _rowset(got) == want
    assert st["recovery.attempts"] >= 2
    assert all(e["action"] == "regrow" for e in st["recovery.events"])
    assert st["recovery.regrow_factor"] >= 2
    assert not st["recovery.fallback"]
    assert any("weld recovery" in str(x.message) for x in w)
    assert faults.fired()[0]["site"] == "join.capacity"


def test_recovery_disabled_surfaces_typed_capacity_error():
    L, R = _join_tables()
    faults.inject("join.capacity", "cap", times=1, value=1)
    with recovery.disabled():
        with pytest.raises(CapacityError):
            Query(L).join(R, on="k", kernelize="always")
    assert recovery.enabled()  # context manager restored the default


def test_recovery_env_knob(monkeypatch):
    try:
        monkeypatch.setenv(recovery.ENV_RECOVERY, "off")
        assert not recovery.enabled()
        monkeypatch.setenv(recovery.ENV_RECOVERY, "1")
        assert recovery.enabled()
        recovery.set_enabled(False)
        assert not recovery.enabled()
        recovery.set_enabled(None)  # back to the env
        assert recovery.enabled()
    finally:
        recovery.set_enabled(None)


def test_injected_decode_poison_recovers_then_exhausts():
    """A decode-site poison is indistinguishable from a real capacity
    poison; one armed hit is absorbed by the retry, while a hit armed
    beyond the ladder's depth exhausts it into a typed error."""
    L, R = _join_tables()
    faults.inject("decode", "poison", times=1)
    st: dict = {}
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        got = Query(L).join(R, on="k", kernelize="off", collect_stats=st)
    assert st["recovery.attempts"] == 2
    assert len(_rowset(got)) == 7
    faults.clear()
    runtime.clear_cache()
    faults.inject("decode", "poison", times=99)  # deeper than the ladder
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        with pytest.raises(CapacityError, match="recovery exhausted"):
            Query(L).join(R, on="k", kernelize="off")


def test_explain_analyze_shows_recovery():
    L, R = _join_tables()
    faults.inject("join.capacity", "cap", times=1, value=1)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        rep = Query(L).explain(analyze=True).join(R, on="k",
                                                  kernelize="always")
    txt = rep.render()
    assert "-- recovery --" in txt
    assert "recovered after" in txt
    assert "regrow" in txt
    assert any(sp.name == "recovery.retry" for sp in rep.spans)
    assert any(sp.name == "recovery.step" for sp in rep.spans)
    assert _rowset(rep.result) == _rowset(
        Query(L).join(R, on="k", kernelize="always"))


# ---------------------------------------------------------------------------
# kernel quarantine
# ---------------------------------------------------------------------------


def test_kernel_fault_degrades_quarantines_and_gates(tmp_path):
    """A kernel launch failure falls back to the generic lowering of the
    SAME program, records the offender on disk, and the next compile
    rejects the quarantined route at the cost gate — without a cache
    clear, proving the quarantine fingerprint invalidates the compile
    cache."""
    L, R = _join_tables()
    want = _rowset(Query(L).join(R, on="k", kernelize="off"))
    qfp0 = quarantine.fingerprint()
    faults.inject("kernel.group_build", "raise", times=1)
    st: dict = {}
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = Query(L).join(R, on="k", kernelize="always", collect_stats=st)
    assert _rowset(got) == want
    assert st["recovery.fallback"]
    assert st["recovery.events"][0]["action"] == "quarantine"
    assert st["recovery.quarantined"]
    assert any("quarantined" in str(x.message) for x in w)
    key = st["recovery.quarantined"][0]
    assert key.startswith("group_build|")
    assert quarantine.is_quarantined("group_build", impl=key.split("|")[1],
                                     dtype=key.split("|")[2], n=1)
    assert (tmp_path / "kernel_health.json").exists()
    assert quarantine.entries()[key]["count"] == 1
    assert "InjectedFault" in quarantine.entries()[key]["last_error"]
    assert quarantine.fingerprint() != qfp0
    # next compile (NO cache clear): the gate rejects the offender up
    # front; the probe kernel is untainted and may still route
    st2: dict = {}
    got2 = Query(L).join(R, on="k", kernelize="always", collect_stats=st2)
    assert _rowset(got2) == want
    kp = st2["kernelplan"]
    assert kp["rejected"].get("group_build") == 1
    assert any(c.get("why") == "quarantined" and not c.get("routed")
               for c in kp["costs"])
    assert "group_build" not in kp.get("routed", {})
    assert "recovery.attempts" not in st2  # healthy run: ladder untouched


def test_quarantine_corrupt_file_degrades_to_empty(tmp_path, monkeypatch):
    p = tmp_path / "kernel_health.json"
    p.write_text("{not json")
    monkeypatch.setattr(quarantine, "_health", None)  # force re-read
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert quarantine.entries() == {}
    hits = [x for x in w if "corrupt" in str(x.message)]
    assert hits, [str(x.message) for x in w]
    # warning parity with the recovery ladder's other RuntimeWarnings:
    # typed, names the offending path, carries the parser error, and
    # tells the user the remedy
    (warning,) = hits
    assert issubclass(warning.category, RuntimeWarning)
    msg = str(warning.message)
    assert str(p) in msg
    assert "Expecting" in msg or "not an object" in msg  # parser detail
    assert "delete the file" in msg
    # a non-dict JSON root takes the same degrade path
    p.write_text("[1, 2, 3]")
    monkeypatch.setattr(quarantine, "_health", None)
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        assert quarantine.entries() == {}
    assert any("not an object" in str(x.message) for x in w2)


def test_quarantine_io_fault_is_best_effort(tmp_path):
    faults.inject("io.quarantine", "raise", times=1)
    quarantine.record("hash_probe", impl="pallas", dtype="f8", n=100,
                      error="x")
    # the write failed, but the quarantine still applies in-process
    assert not (tmp_path / "kernel_health.json").exists()
    assert quarantine.is_quarantined("hash_probe", impl="pallas",
                                     dtype="f8", n=100)
    quarantine.record("hash_probe", impl="pallas", dtype="f8", n=100)
    assert (tmp_path / "kernel_health.json").exists()


# ---------------------------------------------------------------------------
# generic-path overflow parity (satellite: no silent truncation)
# ---------------------------------------------------------------------------


def test_generic_build_overflow_poisons_not_truncates():
    """The generic dictmerger used to silently drop groups past its
    capacity; it must now flag the same negative-count poison the
    kernels do — recovered to the full result, or a typed error."""
    from repro.core import ir, macros as M
    from repro.core.lazy import Evaluate, NewWeldObject

    vals_np = rng.rand(100)

    def mk(capacity):
        keys = NewWeldObject(np.arange(100, dtype=np.int64), None)
        vals = NewWeldObject(vals_np, None)
        kid = ir.Ident(keys.obj_id, keys.weld_type())
        vid = ir.Ident(vals.obj_id, vals.weld_type())
        return NewWeldObject([keys, vals],
                             M.groupby_agg(kid, vid, "+", capacity=capacity))

    want = Evaluate(mk(256), kernelize="off").value
    assert len(want) == 100
    st: dict = {}
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        got = Evaluate(mk(32), kernelize="off", collect_stats=st).value
    assert st["recovery.attempts"] >= 2  # 32 -> 64 -> 128
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-10)
    with recovery.disabled():
        with pytest.raises(CapacityError,
                           match="poisoned|distinct|capacity"):
            Evaluate(mk(32), kernelize="off")


def test_kernel_generic_overflow_parity():
    """Same undersized program, kernel and generic routes: both poison,
    both recover to identical results."""
    from repro.core import ir, macros as M
    from repro.core.lazy import Evaluate, NewWeldObject

    keys_np = (np.arange(300, dtype=np.int64) % 150) * 2
    vals_np = rng.rand(300)

    def mk():
        keys = NewWeldObject(keys_np, None)
        vals = NewWeldObject(vals_np, None)
        kid = ir.Ident(keys.obj_id, keys.weld_type())
        vid = ir.Ident(vals.obj_id, vals.weld_type())
        return NewWeldObject([keys, vals],
                             M.groupby_agg(kid, vid, "+", capacity=64))

    outs = {}
    for mode in ("always", "off"):
        st: dict = {}
        with warnings.catch_warnings(record=True):
            warnings.simplefilter("always")
            outs[mode] = Evaluate(mk(), kernelize=mode,
                                  collect_stats=st).value
        assert st["recovery.attempts"] >= 2, mode
    assert set(outs["always"]) == set(outs["off"])
    assert len(outs["always"]) == 150
    for k in outs["off"]:
        np.testing.assert_allclose(outs["always"][k], outs["off"][k],
                                   rtol=1e-10)


# ---------------------------------------------------------------------------
# best-effort observability paths (satellite: measured-replay tagging)
# ---------------------------------------------------------------------------


def test_measured_replay_failure_is_tagged_not_raised(tmp_path):
    """An injected failure inside EXPLAIN ANALYZE's eager replay must
    land on the measure.replay span as error=..., never propagate, and
    write no bogus ledger record."""
    from repro.core.obs import ledger

    L, R = _join_tables()
    faults.inject("measure.replay", "raise", times=1)
    rep = Query(L).explain(analyze=True).join(R, on="k", kernelize="always")
    got = rep.result
    assert len(_rowset(got)) == 7  # the fault never reached the caller
    replay = [sp for sp in rep.spans if sp.name == "measure.replay"]
    assert replay and "InjectedFault" in replay[0].tags["error"]
    assert ledger.read(str(tmp_path / "ledger.jsonl")) == []


def test_ledger_io_fault_drops_record_not_execution(tmp_path):
    from repro.core.obs import ledger

    faults.inject("io.ledger", "raise", times=1)
    p = str(tmp_path / "ledger.jsonl")
    assert ledger.record("k", "f8", 10, 1, 2, path=p) is None
    assert ledger.read(p) == []
    assert ledger.record("k", "f8", 10, 1, 2, path=p) is not None
    assert len(ledger.read(p)) == 1
