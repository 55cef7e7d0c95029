"""The reduction from a profiler trace to the per-layer numbers, on small
records kept in ``bench/testdata``: the answers are worked out by hand."""

from __future__ import annotations

import sys

import pytest

import benchtools as bt

sys.path.insert(0, str(bt.REPO / "bench"))
import trace_reduce  # noqa: E402


def test_handmade_record():
    rec = trace_reduce.load_record(
        str(bt.REPO / "bench" / "testdata" / "trace_handmade.json"))
    t = trace_reduce.summarize(rec)
    # busy: [1000,1500] + [2000,6000] + [6500,8500] + [10500,11000]
    assert t.devices == 1
    assert t.window_s == pytest.approx(10000e-9)
    assert t.busy_s == pytest.approx(7000e-9)
    assert t.kernel_s == pytest.approx(4000e-9)
    assert t.kernel_calls == 2
    assert t.top_ops[0] == ["region_kernel", pytest.approx(4000e-9)]
    # the loop [6500,8500] holds the kernel [7000,8000]: self time 1000
    assert dict(t.top_ops)["while"] == pytest.approx(1000e-9)
    assert dict(t.top_ops)["copy.2"] == pytest.approx(2000e-9)
    # idle: [1500,2000] [6000,6500] [8500,10500]; every gap's midpoint
    # lies in a serve call
    assert [g[1] for g in t.gaps] == pytest.approx([2000e-9, 500e-9,
                                                    500e-9])
    assert [g[0] for g in t.gaps] == ["bench.serve_call"] * 3
    b = t.breakdown()
    assert len(b["device_ops"]) == 4 and len(b["idle_gaps"]) == 3


def test_record_without_slice_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.summarize({"slice": None, "ops": {}, "host": []})


def test_idle_share_reader_needs_a_device():
    from types import SimpleNamespace
    sys.path.insert(0, str(bt.REPO / "bench"))
    import harness
    reader = harness.load_module(
        bt.REPO / "bench" / "layer_metrics" / "device.idle_share.rows.py",
        "idle_reader")
    rec = trace_reduce.load_record(
        str(bt.REPO / "bench" / "testdata" / "trace_handmade.json"))
    t = trace_reduce.summarize(rec)
    assert reader.read(SimpleNamespace(trace=t)) == pytest.approx(30.0)
    empty = trace_reduce.summarize({"slice": [0, 10], "ops": {}, "host": []})
    assert reader.read(SimpleNamespace(trace=empty)) is None


def test_recorded_v5e_slice():
    """The first 400 device operations of a traced image-edit-bank slice
    on one TPU v5e (``run.py --trace-record``), reduced to the numbers it
    gave when it was recorded."""
    rec = trace_reduce.load_record(
        str(bt.REPO / "bench" / "testdata" / "trace_bank_v5e.json"))
    t = trace_reduce.summarize(rec)
    assert t.devices == 1
    assert t.window_s == pytest.approx(0.00311327)
    assert t.busy_s == pytest.approx(0.000554189)
    assert t.kernel_s == pytest.approx(0.000159382)
    assert t.kernel_calls == 89
    assert t.top_ops[:2] == [["while", pytest.approx(0.00033904)],
                             ["closed_call.22", pytest.approx(0.000158903)]]
    assert t.gaps[0] == ["bench.serve_call", pytest.approx(0.001143036)]
