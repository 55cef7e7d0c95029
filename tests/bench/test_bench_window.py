"""The fit's window, timed by a step counter: it opens at the first step
counted while the job runs, its rate counts only the steps after that,
the window job is sized from the warm-up job's counted steps, and a
counter that moves only when the job ends makes the whole job the window,
said so on an earlier line."""

from __future__ import annotations

import re
import sys
import time

import pytest

import benchtools as bt

sys.path.insert(0, str(bt.REPO / "bench"))
import step_window  # noqa: E402


class FakeJob:
    """A job of ``steps`` steps of ``step_s`` after a start of
    ``start_s``, counting each step as it completes (or all of them at the
    end, with ``at_end``), and noting when each count was made."""

    def __init__(self, steps, step_s, start_s, at_end=False):
        self.steps, self.step_s, self.start_s = steps, step_s, start_s
        self.at_end = at_end
        self.count = 0
        self.counted_at = []

    def counter(self):
        return self.count

    def __call__(self):
        time.sleep(self.start_s)
        for _ in range(self.steps):
            time.sleep(self.step_s)
            if not self.at_end:
                self.count += 1
                self.counted_at.append(time.perf_counter())
        if self.at_end:
            self.count += self.steps
        return "done"


def test_window_opens_at_the_first_counted_step():
    job = FakeJob(steps=5, step_s=0.05, start_s=0.3)
    opened = []
    t = step_window.run_job(job, job.counter, on_open=opened.append)
    assert t.result == "done" and t.opened and t.note is None
    assert opened == [t.t_open]
    # opened just after the first count, before the second
    assert job.counted_at[0] <= t.t_open < job.counted_at[1]
    assert t.n_open - t.n_call == 1
    # the job's start (its call to its first step) is outside the window
    assert t.start_s == pytest.approx(0.35, abs=0.04)


def test_rate_counts_only_the_steps_after_the_opening():
    job = FakeJob(steps=5, step_s=0.05, start_s=0.3)
    t = step_window.run_job(job, job.counter)
    assert t.steps == 4 and t.n_close - t.n_call == 5
    assert t.seconds == pytest.approx(t.t_close - t.t_open)
    assert t.seconds == pytest.approx(0.2, abs=0.04)
    assert t.step_s == pytest.approx(0.05, abs=0.01)


def test_window_job_is_sized_from_the_warm_up_job_counted_steps():
    warm = step_window.run_job(FakeJob(3, 0.05, 0.3), lambda: 0)
    assert not warm.opened          # this counter never moves
    job = FakeJob(steps=3, step_s=0.05, start_s=0.3)
    warm = step_window.run_job(job, job.counter)
    # one step before the window, then about a second of 0.05 s steps;
    # the warm-up's long start does not shrink the job
    assert step_window.steps_for(1.0, warm) == pytest.approx(21, abs=3)


def test_counter_moving_only_at_the_end_times_the_whole_job():
    job = FakeJob(steps=5, step_s=0.05, start_s=0.2, at_end=True)
    t = step_window.run_job(job, job.counter)
    assert not t.opened and t.start_s is None
    assert t.steps == 5
    assert t.seconds == pytest.approx(t.t_close - t.t_call)
    assert "whole job" in t.note


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bt.make_checkout(tmp_path_factory.mktemp("bench"))


def _setup_line(err: str):
    line = next(l for l in err.splitlines() if l.startswith("setup "))
    total = float(re.match(r"setup ([0-9.]+)s", line).group(1))
    parts = dict((k, float(v)) for k, v in
                 re.findall(r"([a-zA-Z -]+?) (-?[0-9.]+)s", line.split(":", 1)[1]))
    return total, {k.strip(): v for k, v in parts.items()}


def test_fit_job_start_is_set_up(root, capsys):
    """Through the harness: the window job's start is a part of set-up,
    and the memory at the window's start is read."""
    line = bt.rehearse(root, "tiny-fit")
    assert line["correct"], line["checks"]
    err = capsys.readouterr().err
    total, parts = _setup_line(err)
    assert 0 < parts["job start"] < total
    assert line["metrics"]["setup_s"]["value"] == pytest.approx(total,
                                                                abs=1e-3)
    assert "whole job" not in err


def test_fit_counter_at_the_end_falls_back(root, capsys, monkeypatch):
    """A fit that counts its steps only when the job ends: the whole job
    is the window, and an earlier line of the run says so."""
    import repro.fit
    from repro.fit import engine
    counted = engine._FIT_STEPS
    whole = engine.fit

    class Silent:
        def inc(self, n=1):
            pass

    def at_end(cf, coords, targets, *, steps, **kw):
        monkeypatch.setattr(engine, "_FIT_STEPS", Silent())
        try:
            res = whole(cf, coords, targets, steps=steps, **kw)
        finally:
            monkeypatch.setattr(engine, "_FIT_STEPS", counted)
        counted.inc(steps)
        return res
    monkeypatch.setattr(engine, "fit", at_end)
    monkeypatch.setattr(repro.fit, "fit", at_end)
    line = bt.rehearse(root, "tiny-fit")
    assert line["correct"], line["checks"]
    err = capsys.readouterr().err
    assert "window: the step counter did not move" in err
    assert line["metrics"]["fit_rows_per_s"]["value"] > 0
    _, parts = _setup_line(err)
    assert "job start" not in parts
