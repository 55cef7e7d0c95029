"""Each traffic generator of the benchmark, run end to end on the CPU at tiny
width (Pallas in interpret mode) through the harness, with the look for a
chip skipped: sound runs come out correct, and a run whose timed path is
broken underneath comes out not correct, once per fault its cell can
have."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import pytest

import benchtools as bt

SERVING = ["tiny-bank", "tiny-o3"]
# the second architecture's cells (benchtools: a tanh MLP, files only)
TANH = ["tiny-tanh-o2", "tiny-tanh-fit"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bt.make_checkout(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", SERVING + ["tiny-fit"] + TANH)
def test_cell_runs_correct(root, cell):
    line = bt.rehearse(root, cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["device"]["kind"] and line["device"]["count"] >= 1
    assert line["metrics"]["setup_s"]["value"] > 0


def _alter_answers(monkeypatch):
    """Every block and chunk the pipeline serves returns its first row's
    first output changed."""
    from repro.core import pipeline

    init = pipeline.CompiledGradient.__init__

    def altered(fn):
        def call(x):
            outs = fn(x)
            first = outs[0]
            idx = (0,) * (first.ndim - 1)
            return (first.at[idx].add(1.0),) + tuple(outs[1:])
        return call

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        self._chunk_apply = altered(self._chunk_apply)
        self._block_apply = altered(self._block_apply)
    monkeypatch.setattr(pipeline.CompiledGradient, "__init__", patched)


@pytest.mark.parametrize("cell", SERVING + ["tiny-tanh-o2"])
def test_altered_answer_is_not_correct(root, cell, monkeypatch):
    _alter_answers(monkeypatch)
    line = bt.rehearse(root, cell)
    assert not line["correct"], line["checks"]


def test_unchanged_state_is_not_correct(root, monkeypatch):
    """A fit step that returns its parameters and state unchanged."""
    from repro.fit import engine

    def frozen(cfg, params, grads, opt, step):
        return params, opt, jnp.zeros(())
    monkeypatch.setattr(engine, "adamw_update", frozen)
    line = bt.rehearse(root, "tiny-fit")
    checks = line["checks"]
    assert not line["correct"], checks
    curve = checks["curve_head_gap"]
    assert curve["value"] > curve["limit"], checks


def test_half_batch_is_not_correct(root, monkeypatch):
    """Each step's loss and gradient taken over half its rows."""
    from repro.fit import engine
    whole = engine._chunk_vg

    def half(cf, leaves, xc, yc, mc, n_rows):
        h = xc.shape[0] // 2
        return whole(cf, leaves, xc[:h], yc[:h], mc[:h], jnp.sum(mc[:h]))
    monkeypatch.setattr(engine, "_chunk_vg", half)
    line = bt.rehearse(root, "tiny-fit")
    assert not line["correct"], line["checks"]


def test_updates_stopped_after_warmup_are_not_correct(root, monkeypatch):
    """Steps after the warm-up's first ones leave the state unchanged: the
    warm-up job is sound, and only the window job's own numbers see it."""
    from repro.fit import engine
    update = engine.adamw_update

    def stops(cfg, params, grads, opt, step):
        new, opt2, norm = update(cfg, params, grads, opt, step)
        keep = step < 3
        pick = partial(jax.tree.map, lambda a, b: jnp.where(keep, a, b))
        return pick(new, params), pick(opt2, opt), norm
    monkeypatch.setattr(engine, "adamw_update", stops)
    # a window long enough for steps past the warm-up's on a loaded host
    line = bt.rehearse(root, "tiny-fit", seconds=8.0)
    checks = line["checks"]
    grad = checks["grad1_gap"]
    assert grad["value"] <= grad["limit"]
    assert not line["correct"], checks
    assert (checks["curve_head_gap"]["value"]
            > checks["curve_head_gap"]["limit"]), checks


def test_fewer_steps_than_asked_are_not_correct(root, monkeypatch):
    """A job that takes one step fewer than it was asked for."""
    from repro.fit import engine
    whole = engine.fit

    def short(cf, coords, targets, *, steps, **kw):
        return whole(cf, coords, targets, steps=max(1, steps - 1), **kw)
    monkeypatch.setattr(engine, "fit", short)
    import repro.fit
    monkeypatch.setattr(repro.fit, "fit", short)
    line = bt.rehearse(root, "tiny-fit")
    assert not line["correct"], line["checks"]
    assert line["checks"]["steps_gap"]["value"] == 1.0


def _split(x):
    """x as a pair of bf16 values, hi + lo (16 significant bits)."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def bf16x3_dot(a, b):
    """The CPU computes every float32 dot in full, whatever precision it
    is asked for.  This stands in for a TPU's three-pass bf16 product,
    hi*hi + hi*lo + lo*hi of the operands' bf16 pairs, in every dot and
    in every dot its derivatives take."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    dot = partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def _dot_fwd(a, b):
    return bf16x3_dot(a, b), (a, b)


def _dot_bwd(res, ct):
    a, b = res
    return bf16x3_dot(ct, b.T), bf16x3_dot(a.T, ct)


bf16x3_dot.defvjp(_dot_fwd, _dot_bwd)


@pytest.mark.parametrize("cell", SERVING)
def test_control_is_not_correct(root, cell):
    """The reference in the program's place, one precision step down,
    comes out not correct through the harness's own judgement."""
    line = bt.rehearse(root, cell, control={"precision": "high",
                                            "dot": bf16x3_dot})
    assert line["correct"], line["checks"]
    got = line["controls"]["control"]
    assert got["correct"] is False, got


def test_fit_control_separates(root):
    """The reference in the program's place, one precision step down and
    with each fault planted in it, through the harness's judgement: each
    comes out not correct against the cell's own limits, the control by
    its first gradient."""
    line = bt.rehearse(root, "tiny-fit", control={"precision": "high",
                                                  "dot": bf16x3_dot})
    assert line["correct"], line["checks"]
    got = line["controls"]
    assert set(got) == {"control", "half_batch", "unchanged_state"}
    assert {v: g["correct"] for v, g in got.items()} == {
        "control": False, "half_batch": False, "unchanged_state": False}
    grad = got["control"]["checks"]["grad1_gap"]
    assert grad["value"] > grad["limit"], got["control"]


def test_traced_fit_slices_its_steps(root):
    """A traced fit run opens its slice once the job is stepping, by the
    program's step counter: the slice's work is read, and the run is
    judged as an untraced one is."""
    line = bt.rehearse(root, "tiny-fit", trace=1, seconds=2.0)
    assert line["correct"], line["checks"]
    assert "mfu.fit" in line["metrics"], line["metrics"]
