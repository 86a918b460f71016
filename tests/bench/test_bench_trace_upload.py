"""The time a host-to-device upload is in flight, and ``upload_ms``, on
267 ms recorded on a TPU v5e in the profiled half of a ``tpch-sf10.q6``
run (one round of three requests: twelve columns relaid out, their
copies issued and landing one by one, then fifteen ops), and on hand-made
traces.  Every expected number is worked out by hand from the events."""
from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from bench import harness, trace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_v5e_q6_upload.json")


@pytest.fixture(scope="module")
def events():
    with open(FIXTURE) as f:
        return json.load(f)


def test_upload_runs_from_the_first_relayout_to_the_last_copy(events):
    # the round's first XlaLinearize starts at 7,922,015 ns; its twelve
    # copies are issued from 52,239,691 ns and the last lands at
    # 255,550,757 + 33,629 ns, with one always in flight in between; the
    # next round's relayout starts at 263,400,486 ns and runs past the
    # window's end at 267,000,000 ns
    assert trace.union(trace.uploads(events), 0, 267_000_000) == [
        [7_922_015, 255_584_386], [263_400_486, 267_000_000]]
    assert trace.reduce(events)["upload_s"] == pytest.approx(
        (255_584_386 - 7_922_015 + 267_000_000 - 263_400_486) * 1e-9)


def test_a_copy_done_before_any_issue_is_not_paired(events):
    # the done at 148,285 ns is of a copy issued before the cut; paired
    # with the first issue it would end the in-flight time one copy early
    copies = trace.uploads(dict(events, host=[
        h for h in events["host"] if h[0] != trace.LINEARIZE]))
    assert copies == [(52_239_691, 255_584_386)]


def test_the_idle_gap_is_named_by_the_upload(events):
    # the device idles from the last op of the round, which ends at
    # 10,171,697 + 1,073,652 ns, to the next round's first at 254,091,023
    gaps = trace.reduce(events)["breakdown"]["idle_gaps"]
    assert gaps[0] == [trace.UPLOAD, pytest.approx(242_845_674e-9)]


US = 1_000  # ns


def _host(*evs):
    return {"chips": 1, "device": [],
            "host": [["bench.window", 0, 1000 * US], *evs]}


def test_copies_landing_out_of_order_count_while_any_is_in_flight():
    # copies issued at 100 and 200 us, done at 250 and 400 us, whichever
    # landed first; a third issued at 600, done at 700
    ev = _host([trace.H2D_ISSUE, 100 * US, 1 * US],
               [trace.H2D_ISSUE, 200 * US, 1 * US],
               [trace.H2D_DONE, 249 * US, 1 * US],
               [trace.H2D_DONE, 399 * US, 1 * US],
               [trace.H2D_ISSUE, 600 * US, 1 * US],
               [trace.H2D_DONE, 699 * US, 1 * US])
    assert trace.uploads(ev) == [(100 * US, 400 * US), (600 * US, 700 * US)]
    assert trace.reduce(ev)["upload_s"] == pytest.approx(400e-6)


def test_upload_ms_is_the_in_flight_time_per_query(tmp_path):
    cell = harness.load_cell("tpch-sf10.q6", bench_tiny.tiny_root(tmp_path))
    read = {m["name"]: m["reader"].read
            for m in cell["per_layer"]}["upload_ms"]
    assert read({"device_trace": {"upload_s": 0.25, "queries": 3}}) == \
        pytest.approx(0.25 / 3 * 1e3)
    # nothing uploaded in the window: nothing to read
    assert read({"device_trace": {"upload_s": 0.0, "queries": 3}}) is None
