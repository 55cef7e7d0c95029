"""The device's idle time split by the program's own spans
(``bench/span_split.py``) and the per-layer readers of that split, on
records worked out by hand, and the program's spans read back from a
traced CPU rehearsal."""

from __future__ import annotations

import sys
from types import SimpleNamespace

import pytest

import benchtools as bt

sys.path.insert(0, str(bt.REPO / "bench"))
import harness  # noqa: E402
import span_split  # noqa: E402
import trace_reduce  # noqa: E402

HANDMADE = bt.REPO / "bench" / "testdata" / "trace_spans_handmade.json"

# a fit step on two devices: on the first, [0,100] is idle while the host
# gathers and dispatches, [900,1000] while it waits on the loss; the
# second is busy throughout, so each idle share halves
FIT_REC = {
    "slice": [0, 1000],
    "ops": {"/device:TPU:0": [["transpose_jvp", 100, 800, True]],
            "/device:TPU:1": [["transpose_jvp", 0, 1000, True]]},
    "host": [],
    "spans": [["fit.step", 0, 1000], ["fit.gather", 0, 50],
              ["fit.dispatch", 50, 70], ["fit.sync", 120, 880]],
}


def _reader(name: str):
    return harness.load_module(bt.REPO / "bench" / "layer_metrics"
                               / f"{name}.py",
                               "span_reader_" + name.replace(".", "_"))


def _summary(rec: dict):
    """What a reader is handed: the summary, with the split beside it."""
    idle, span_s = span_split.split(rec)
    return SimpleNamespace(**vars(trace_reduce.summarize(rec)),
                           idle_by_span=idle, span_s=span_s)


def test_handmade_idle_split():
    rec = trace_reduce.load_record(str(HANDMADE))
    idle, span_s = span_split.split(rec)
    # idle [0,1000] [2000,4000] [5000,8000] [9000,10000]; serve.group opens
    # before the slice, and [2000,4000] spans two chunks, a block and the
    # stitch; bench.serve_call is the benchmark's, not the program's
    want = {"serve.group": 400, "outside": 200 + 600, "serve.dispatch": 100,
            "pipeline.pad": 300, "pipeline.chunk": 500 + 800,
            "pipeline.block": 200, "pipeline.stitch": 500 + 1000,
            "serve.wait": 2000 + 200, "serve.unpad": 200}
    assert idle == pytest.approx({k: v / 1e9 for k, v in want.items()})
    t = trace_reduce.summarize(rec)
    assert sum(idle.values()) == pytest.approx(t.window_s - t.busy_s)
    assert span_s["pipeline.chunk"] == [2, pytest.approx(1800e-9)]
    assert span_s["serve.group"] == [1, pytest.approx(400e-9)]
    assert span_s["serve.dispatch"] == [1, pytest.approx(5400e-9)]


def test_idle_split_averages_devices():
    idle, span_s = span_split.split(FIT_REC)
    assert idle == pytest.approx({"fit.gather": 25e-9, "fit.dispatch": 25e-9,
                                  "fit.sync": 50e-9})
    t = trace_reduce.summarize(FIT_REC)
    assert t.devices == 2
    assert sum(idle.values()) == pytest.approx(t.window_s - t.busy_s)
    assert span_s["fit.step"] == [1, pytest.approx(1000e-9)]


@pytest.mark.parametrize("metric, want", [
    ("pipeline.idle_share.rows", 33.0),       # 300 + 1300 + 200 + 1500
    ("engines.idle_share.rows", 29.0),        # 400 + 100 + 2200 + 200
    ("pipeline.enqueue_ms_per_krow.rows", 0.002),  # 2,000 ns over 1 krow
])
def test_serving_span_readers(metric, want):
    t = _summary(trace_reduce.load_record(str(HANDMADE)))
    got = _reader(metric).read(SimpleNamespace(trace=t,
                                               work={"rows": 1000}))
    assert got == pytest.approx(want)


def test_fit_span_reader():
    got = _reader("engines.idle_share.fit").read(
        SimpleNamespace(trace=_summary(FIT_REC), work={"rows": 250}))
    assert got == pytest.approx(10.0)


@pytest.mark.parametrize("metric", [
    "pipeline.idle_share.rows", "engines.idle_share.rows",
    "pipeline.enqueue_ms_per_krow.rows", "engines.idle_share.fit"])
def test_span_readers_report_nothing_without_spans(metric):
    """A slice of a program that opens no such span (or a summary that
    does not carry the split) reads as nothing, not as 0."""
    rec = trace_reduce.load_record(
        str(bt.REPO / "bench" / "testdata" / "trace_handmade.json"))
    reader = _reader(metric)
    for t in (trace_reduce.summarize(rec), _summary(dict(rec, spans=[]))):
        assert reader.read(SimpleNamespace(trace=t,
                                           work={"rows": 1000})) is None


def test_program_spans_reach_a_traced_slice(tmp_path, monkeypatch):
    """A traced CPU rehearsal of the order-3 tiny cell: the program's
    engine and pipeline spans are in the profiler's trace, inside the
    slice, and the split covers the slice (no device plane on the CPU, so
    all of it is idle)."""
    root = bt.make_checkout(tmp_path)
    run = bt.load_run(root)
    import trace_reduce as tr          # the module run.py will import
    got = {}
    extract = tr.extract

    def keep_spans(trace_dir):
        rec = extract(trace_dir)
        got["rec"] = dict(rec, spans=span_split.spans(trace_dir))
        return rec

    monkeypatch.setattr(tr, "extract", keep_spans)
    args = SimpleNamespace(workload="tiny-o3", seed=5, seconds=2.0, trace=1,
                           trace_record=None)
    line = run.execute(args, root=root, chip=False, peaks=bt.CPU_PEAKS)
    assert line["correct"], line["checks"]
    assert "rec" in got
    idle, span_s = span_split.split(got["rec"])
    assert {"serve.group", "serve.pad", "serve.dispatch", "serve.wait",
            "serve.unpad", "pipeline.pad", "pipeline.stitch"} <= set(span_s)
    assert "pipeline.chunk" in span_s or "pipeline.block" in span_s
    lo, hi = got["rec"]["slice"]
    assert sum(idle.values()) == pytest.approx((hi - lo) / 1e9)
