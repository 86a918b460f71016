"""One cell of the benchmark: set-up, the measured window, the check.

A cell is found by name.  ``BENCHMARK.json`` gives its configuration and
traffic; ``configs/<config>.json`` the deployment and its number of
streams, ``mixes/<mix>.json`` the query, its parameters and the limits
of the check; ``queries/<query>.py`` builds the served query and its plain
reference; ``metrics/<metric>.py`` reads one metric from the run.

Every request is built through ``weldrel`` and served by one
``QueryServer`` with the default ``kernelize`` and ``kernel_impl``.  The
traffic is the TPC-H throughput test's: closed-loop streams, each
sending its next query as soon as the last one returned.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(RuntimeError):
    """No accelerator, too few of them, or one without published peaks."""


# -- finding a cell by name ---------------------------------------------------


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _for_cell(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell needs, found by name under ``root``."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    mix = _json(os.path.join(root, "bench", "mixes", w["traffic"] + ".json"))

    def metrics(kind):
        return [dict(m, reader=_module(
                    os.path.join(root, "bench", "metrics", m["name"] + ".py"),
                    f"bench_metric_{m['name']}"))
                for m in bench[kind] if _for_cell(m, name)]

    return {
        "name": name, "chips": w["chips"], "config": config, "mix": mix,
        "streams": config["streams"], "params": mix["params"],
        "query": _module(os.path.join(root, "bench", "queries",
                                      mix["query"] + ".py"),
                         f"bench_query_{mix['query']}"),
        "end_to_end": metrics("end_to_end"),
        "per_layer": metrics("per_layer"),
    }


# -- the device ---------------------------------------------------------------


def device_info(chips: int) -> dict:
    """The accelerator JAX reports, with its peaks; raises ``NoChip``
    without a TPU, with fewer chips than the cell asks for, or for a
    device kind that ``peaks.py`` does not hold."""
    import jax

    from bench import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    try:
        pk = peaks.peaks_of(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from e
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "peaks": pk}


def memory_peak_bytes() -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


# -- the traffic --------------------------------------------------------------


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def drive(srv, cell: dict, tables: dict, seconds: float) -> dict:
    """Closed-loop streams for ``seconds``: each stream builds its query
    through weldrel, submits it and waits for the answer, then starts the
    next.  Requests started before the deadline run to their end.

    Returns the window's start and, per request, ``(start, built,
    done, answer, error)`` on the ``perf_counter`` clock."""
    query, params = cell["query"], cell["params"]
    records: list = []
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def stream():
        while True:
            ts = time.perf_counter()
            if ts >= deadline:
                return
            answer = error = None
            with _annotate("bench.build_query"):
                sq = query.build(tables, params)
            tb = time.perf_counter()
            try:
                with _annotate("bench.wait_result"):
                    answer = srv.submit(sq).result()
            except Exception as e:  # noqa: BLE001 - counted as failed
                error = f"{type(e).__name__}: {e}"
            records.append((ts, tb, time.perf_counter(), answer, error))

    threads = [threading.Thread(target=stream, name=f"stream{i}")
               for i in range(cell["streams"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"t0": t0, "records": records}


def window_stats(win: dict) -> dict:
    """Latencies and completions of one window; an answer that failed
    counts as not completed (``ok`` is filled in by the check).
    ``stall_s`` is the longest time in which no request completed, and
    when in the window it began."""
    recs = win["records"]
    done = sorted(te - win["t0"] for _, _, te, _, _ in recs)
    edges = [0.0] + done
    return {
        "latencies_s": [te - ts for ts, _, te, _, _ in recs],
        "last_done_s": done[-1] if done else 0.0,
        "stall_s": max(((b - a, a) for a, b in zip(edges, edges[1:])),
                       default=(0.0, 0.0)),
    }


# -- the check ----------------------------------------------------------------


def check(cell: dict, records: list, want) -> dict:
    """Compare every answer with the reference: the worst of each number
    beside its limit, and how many answers failed."""
    from bench import compare as cmp

    query, limits = cell["query"], cell["mix"]["limits"]
    readings, failed, errors = [], 0, []
    for _, _, _, answer, error in records:
        if error is not None:
            failed += 1
            errors.append(error)
            continue
        r = query.compare(answer, want)
        readings.append(r)
        if any(r[k] > limits[k] for k in limits):
            failed += 1
    worst = cmp.worst(readings)
    numbers = {k: {"value": worst.get(k), "limit": v}
               for k, v in limits.items()}
    return {"numbers": numbers, "failed": failed, "errors": errors[:3],
            "ok": len(records) - failed}


# -- one run ------------------------------------------------------------------


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def prepare(cell: dict, seed: int) -> tuple:
    """The seeded host columns the cell's query reads, and its tables."""
    from bench import tpch_gen

    cfg, query = cell["config"], cell["query"]
    reads = query.reads(cell["params"])
    for t, cols in reads.items():
        missing = set(cols) - set(cfg["columns"].get(t, {}))
        if missing:
            raise KeyError(f"{cell['name']}: configuration "
                           f"{cfg['name']!r} holds no {t}.{sorted(missing)}")
    data = tpch_gen.make_tables(cfg["scale_factor"], seed, reads)
    return data, query.tables(data, cell["params"])


def warm_up(srv, cell: dict, tables: dict) -> dict:
    """Compile the cell's query once, then serve one request per stream
    at once; returns the plan's kernel routing."""
    cq = cell["query"].build(tables, cell["params"]).compile()
    for f in [srv.submit(cell["query"].build(tables, cell["params"]))
              for _ in range(cell["streams"])]:
        f.result()
    kp = cq.stats.get("kernelplan", {})
    return {"impl": kp.get("impl"),
            "routed": {k: v for k, v in sorted(kp.get("routed", {}).items())}}


def _span_seconds(spans: list, names) -> dict:
    out: dict = {n: [] for n in names}
    for sp in spans:
        if sp.name in out and sp.dur_ns is not None:
            out[sp.name].append(sp.dur_ns / 1e9)
    return out


def _span_totals(spans: list, top: int = 12) -> dict:
    """Seconds per weldtrace span name, the largest first."""
    tot: dict = {}
    for sp in spans:
        if sp.dur_ns:
            tot[sp.name] = tot.get(sp.name, 0) + sp.dur_ns / 1e9
    return dict(sorted(tot.items(), key=lambda kv: -kv[1])[:top])


def _profile(srv, cell, tables, seconds, trace_dir):
    import jax

    from bench import trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with _annotate(trace.WINDOW):
            win = drive(srv, cell, tables, seconds)
    finally:
        jax.profiler.stop_trace()
    return win, trace.load(trace_dir)


def run_cell(cell: dict, seed: int, seconds: float, traced: bool,
             device: dict, started: float, log=None) -> dict:
    """Set-up, the window and the check of one run; the result line."""
    from repro.core import obs, runtime
    from repro.core.serve import QueryServer

    from bench import trace

    log = log or _log
    obs.disable()
    if traced:
        obs.enable()
    mark = obs.mark()
    data, tables = prepare(cell, seed)
    srv = QueryServer(workers=cell["streams"])
    try:
        plan = warm_up(srv, cell, tables)
        setup_spans = obs.spans_since(mark)
        run = {"setup_s": time.perf_counter() - started,
               "setup_spans": _span_seconds(setup_spans, ["weld.compile"]),
               "peaks": device["peaks"]}
        obs.disable()
        log(f"[bench] {cell['name']} seed={seed}: set-up "
            f"{run['setup_s']:.1f} s, plan {plan}")
        misses = runtime.cache_stats()["cache.misses"]
        if not traced:
            win = drive(srv, cell, tables, seconds)
            records = win["records"]
            run["window"] = window_stats(win)
        else:
            tmp = tempfile.mkdtemp(prefix="bench-trace-")
            try:
                win, events = _profile(srv, cell, tables, seconds / 2, tmp)
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
            reduced = trace.reduce(events)
            run["device_trace"] = dict(reduced, queries=len(win["records"]))
            obs.enable()
            mark = obs.mark()
            win2 = drive(srv, cell, tables, seconds / 2)
            spans = _span_seconds(obs.spans_since(mark), ["encode", "decode"])
            obs.disable()
            spans["frames"] = [tb - ts for ts, tb, _, _, _ in win2["records"]]
            run["spans"], run["span_queries"] = spans, len(win2["records"])
            records = win["records"] + win2["records"]
        window_misses = runtime.cache_stats()["cache.misses"] - misses
        peak = memory_peak_bytes()
    finally:
        srv.close()
    want = cell["query"].reference(data, cell["params"])
    checked = check(cell, records, want)
    run["essential_bytes"] = cell["query"].essential_bytes(
        data, cell["params"], want)
    if "window" in run:
        run["window"]["n_ok"] = checked["ok"]
    print(json.dumps({"info": {
        "cell": cell["name"], "seed": seed, "trace": int(traced),
        "kernel_impl": plan["impl"], "routed": plan["routed"],
        "window_cache_misses": window_misses,
        "stall_s": window_stats(win)["stall_s"],
        "attempted": len(records), "errors": checked["errors"],
        "setup_spans_s": _span_totals(setup_spans)}}),
        flush=True)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell[kind]:
        v = m["reader"].read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = {k: device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = peak
    result = {"correct": checked["failed"] == 0 and len(records) > 0,
              "attempted": len(records), "failed": checked["failed"],
              "metrics": metrics, "device": dev}
    if traced:
        dev["busy_s"] = run["device_trace"]["busy_s"]
        dev["window_s"] = run["device_trace"]["window_s"]
        result["breakdown"] = run["device_trace"]["breakdown"]
    result["checks"] = checked["numbers"]
    return result


@contextlib.contextmanager
def fresh_state(root: str = ROOT):
    """The program's on-disk state for one run: JAX's compile cache and
    Weld's autotune cache at fixed paths in the checkout (found again by
    the next run), a new cost ledger and kernel-health file (so neither
    calibration nor quarantine carries over from another run)."""
    cache = os.path.join(root, ".jax_cache")
    tmp = tempfile.mkdtemp(prefix="bench-state-")
    env = {
        "JAX_COMPILATION_CACHE_DIR": os.path.join(cache, "xla"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
        "WELD_AUTOTUNE_CACHE": os.path.join(cache, "weld_autotune.json"),
        "WELD_COST_LEDGER": os.path.join(tmp, "cost_ledger.jsonl"),
        "WELD_KERNEL_HEALTH": os.path.join(tmp, "kernel_health.json"),
    }
    old = {k: os.environ.get(k) for k in list(env) + ["WELD_TRACE"]}
    os.environ.update(env)
    os.environ.pop("WELD_TRACE", None)
    try:
        yield env
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        shutil.rmtree(tmp, ignore_errors=True)


def print_checks(result: dict) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)

