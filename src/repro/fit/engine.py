"""Fit engine — epochs of shuffled block chunks through AdamW (DESIGN.md §11).

``fit`` drives one ``CompiledFit`` artifact: each step streams a chunk of
coordinate blocks through the artifact's online loss-gradient program and
applies one ``optim.adam.adamw_update``.  With ``batch_rows=None`` every
step sees the whole grid (still streamed — peak memory stays
O(block x depth)), which makes a streamed fit bit-for-bit comparable to a
whole-grid ``jax.grad`` loop at equal step counts; with ``batch_rows`` set,
epochs visit equal-sized chunks of a per-epoch block shuffle (wrap-around
keeps every chunk the same shape, so ONE jitted step serves the whole run).

``fit_many`` is the K-batched variant: K weight sets of one architecture
fit CONCURRENTLY by vmapping the flat-leaf step over a stacked [K, ...]
leaf axis — the same stacked-K machinery ``MultiINRArtifact`` serves with.
All K lanes share the coordinate grid and the shuffle schedule, so the
vmapped math is the sequential math, just batched (tests gate allclose).

Converged weights stream straight into ``ArtifactStore.put_weights`` —
fit -> store -> serve without a re-trace, the store's first write-heavy
production loop.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.fit.compile import CompiledFit
from repro.obs import metrics
from repro.obs.tracing import TRACER
from repro.optim.adam import AdamWConfig, adamw_update, init_opt_state

_FIT_STEPS = metrics.counter(
    "fit_steps", "optimizer steps taken by the fit engine")
_FIT_PUTS = metrics.counter(
    "fit_weight_puts", "fitted weight payloads streamed into a store")
_PEAK = metrics.gauge(
    "fit_peak_bytes", "modeled peak fit memory (streamed path)")
_LAT_STEP = metrics.histogram(
    "fit_step_latency_s", "wall-clock seconds per fit step")


@dataclass
class FitResult:
    """One fit run: final params (caller's pytree), per-step mean losses,
    and the artifact signature the weights serve under."""
    params: object
    losses: list[float]
    steps: int
    signature: str
    inr_id: str | None = None
    wall_s: float = 0.0
    meta: dict = field(default_factory=dict)

    @property
    def final_loss(self) -> float:
        return self.losses[-1] if self.losses else float("nan")


def _chunk_schedule(n_blocks: int, chunk_blocks: int, steps: int, key):
    """Per-step block-index chunks: each epoch shuffles the block order,
    steps consume ``chunk_blocks``-sized windows with wrap-around (every
    chunk the same shape -> one jitted step for the whole run)."""
    out = []
    perm = None
    pos = 0
    k = key
    for _ in range(steps):
        if perm is None or pos + chunk_blocks > n_blocks:
            k, sub = jax.random.split(k)
            perm = np.asarray(jax.random.permutation(sub, n_blocks))
            pos = 0
        if chunk_blocks >= n_blocks:
            idx = np.resize(perm, chunk_blocks)
        else:
            idx = perm[pos:pos + chunk_blocks]
            pos += chunk_blocks
        out.append(idx)
    return out


def _prepare(cf: CompiledFit, coords, targets):
    """Block the grid once on the host; steps gather chunks by block index."""
    xb, yb, mb, n = cf._blocked(jnp.asarray(coords), targets)
    return xb, yb, mb, n


def fit(cf: CompiledFit, coords, targets, *, steps: int,
        params=None, adam: AdamWConfig | None = None, key=None,
        batch_rows: int | None = None, store=None,
        inr_id: str | None = None) -> FitResult:
    """Fit one weight set.  ``params`` defaults to the compile template;
    ``batch_rows=None`` streams the WHOLE grid every step (equal-step
    parity with a whole-grid baseline), otherwise each step visits a
    shuffled ~``batch_rows`` chunk.  With ``store``/``inr_id`` the fitted
    payload is written for immediate serving."""
    if adam is None:
        adam = AdamWConfig(total_steps=max(steps, 1), warmup_steps=0,
                           weight_decay=0.0)
    if key is None:
        key = jax.random.PRNGKey(0)
    leaves = list(cf.leaves_of(params if params is not None
                               else cf.unflatten(cf.template_leaves)))
    _PEAK.max(float(cf.peak_bytes()))

    block = cf.config.block
    xb, yb, mb, _ = _prepare(cf, coords, targets)
    n_blocks = xb.shape[0]
    if batch_rows is None:
        chunks = None
    else:
        cb = max(1, min(n_blocks, -(-batch_rows // block)))
        chunks = _chunk_schedule(n_blocks, cb, steps, key)

    @jax.jit
    def step_fn(lv, opt, i, xc, yc, mc):
        n_rows = jnp.sum(mc)
        loss, gs = _chunk_vg(cf, lv, xc, yc, mc, n_rows)
        new, opt, _ = adamw_update(adam, list(lv), list(gs), opt, i)
        return tuple(new), opt, loss

    opt = init_opt_state(leaves)
    losses = []
    t0 = time.perf_counter()
    with TRACER.span("fit.run", cat="fit", steps=steps,
                     order=cf.order, loss=type(cf.loss).__name__):
        lv = tuple(leaves)
        for i in range(steps):
            with TRACER.span("fit.step", cat="fit"):
                ts = time.perf_counter()
                with TRACER.span("fit.gather", cat="fit"):
                    if chunks is None:
                        xc, yc, mc = xb, yb, mb
                    else:
                        idx = chunks[i]
                        xc, yc, mc = xb[idx], yb[idx], mb[idx]
                with TRACER.span("fit.dispatch", cat="fit"):
                    lv, opt, loss = step_fn(lv, opt, i, xc, yc, mc)
                with TRACER.span("fit.sync", cat="fit"):
                    losses.append(float(loss))
                # the step counts once its loss is on the host
                _FIT_STEPS.inc()
                _LAT_STEP.observe(time.perf_counter() - ts)
    wall = time.perf_counter() - t0

    final = cf.unflatten(lv)
    if store is not None and inr_id is not None:
        with TRACER.span("fit.put_weights", cat="fit", inr_id=inr_id):
            store.put_weights(cf.signature, inr_id, cf.payload(final))
        _FIT_PUTS.inc()
    return FitResult(params=final, losses=losses, steps=steps,
                     signature=cf.signature, inr_id=inr_id, wall_s=wall,
                     meta={"peak_model_bytes": cf.peak_bytes()})


def _chunk_vg(cf: CompiledFit, leaves, xc, yc, mc, n_rows):
    """Mean loss + leaf grads over one pre-blocked chunk — the scan-carry
    accumulation of ``CompiledFit._stream_vg`` on gathered blocks."""
    C, D = cf.out_features, cf.in_features

    def block_loss(lv, xblk, yblk, mblk):
        res_env = cf._res_env(lv)
        outs = cf._block_fn(res_env, xblk)
        return jnp.sum(cf.loss.row_loss(outs, yblk, C, D) * mblk)

    zeros = tuple(jnp.zeros_like(l) for l in leaves)

    def body(carry, inp):
        ls, gs = carry
        l, gl = jax.value_and_grad(block_loss)(tuple(leaves), *inp)
        return (ls + l, tuple(a + b for a, b in zip(gs, gl))), None

    (ls, gs), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zeros),
                               (xc, yc, mc))
    n = jnp.maximum(n_rows.astype(jnp.float32), 1.0)
    return ls / n, tuple(g / n for g in gs)


def fit_many(cf: CompiledFit, params_list, coords, targets_list, *,
             steps: int, adam: AdamWConfig | None = None, key=None,
             batch_rows: int | None = None, store=None,
             inr_ids=None) -> list[FitResult]:
    """Fit K weight sets of one architecture CONCURRENTLY: leaves stack on a
    leading [K] axis and the whole optimizer step runs under ``jax.vmap`` —
    the MultiINRArtifact stacked-K idiom applied to training.  Every lane
    shares the grid and the shuffle schedule, so lane k's trajectory is
    exactly ``fit``'s with the same key.  Targets are per-lane."""
    if adam is None:
        adam = AdamWConfig(total_steps=max(steps, 1), warmup_steps=0,
                           weight_decay=0.0)
    if key is None:
        key = jax.random.PRNGKey(0)
    K = len(params_list)
    if len(targets_list) != K:
        raise ValueError(f"{K} params vs {len(targets_list)} targets")
    flat = [cf.leaves_of(p) for p in params_list]
    stacked = tuple(jnp.stack([flat[k][i] for k in range(K)])
                    for i in range(len(flat[0])))
    _PEAK.max(float(cf.peak_bytes()) * K)

    block = cf.config.block
    xb, _, mb, _ = _prepare(cf, coords, targets_list[0])
    ybs = jnp.stack([cf._blocked(jnp.asarray(coords), t)[1]
                     for t in targets_list])
    n_blocks = xb.shape[0]
    if batch_rows is None:
        chunks = None
    else:
        cb = max(1, min(n_blocks, -(-batch_rows // block)))
        chunks = _chunk_schedule(n_blocks, cb, steps, key)

    def lane_step(lv, opt, i, xc, yc, mc):
        n_rows = jnp.sum(mc)
        loss, gs = _chunk_vg(cf, lv, xc, yc, mc, n_rows)
        new, opt, _ = adamw_update(adam, list(lv), list(gs), opt, i)
        return tuple(new), opt, loss

    step_fn = jax.jit(jax.vmap(lane_step,
                               in_axes=(0, 0, None, None, 0, None)))

    # zeros_like of the stacked leaves IS the stacked per-lane state
    opt = init_opt_state(list(stacked))
    losses = [[] for _ in range(K)]
    t0 = time.perf_counter()
    with TRACER.span("fit.run_many", cat="fit", k=K, steps=steps,
                     order=cf.order):
        lv = stacked
        for i in range(steps):
            with TRACER.span("fit.step", cat="fit"):
                ts = time.perf_counter()
                with TRACER.span("fit.gather", cat="fit"):
                    if chunks is None:
                        xc, yc, mc = xb, ybs, mb
                    else:
                        idx = chunks[i]
                        xc, yc, mc = xb[idx], ybs[:, idx], mb[idx]
                with TRACER.span("fit.dispatch", cat="fit"):
                    lv, opt, loss = step_fn(lv, opt, i, xc, yc, mc)
                with TRACER.span("fit.sync", cat="fit"):
                    for k in range(K):
                        losses[k].append(float(loss[k]))
                _FIT_STEPS.inc(K)
                _LAT_STEP.observe(time.perf_counter() - ts)
    wall = time.perf_counter() - t0

    results = []
    for k in range(K):
        final = cf.unflatten([l[k] for l in lv])
        iid = inr_ids[k] if inr_ids is not None else None
        if store is not None and iid is not None:
            store.put_weights(cf.signature, iid, cf.payload(final))
            _FIT_PUTS.inc()
        results.append(FitResult(
            params=final, losses=losses[k], steps=steps,
            signature=cf.signature, inr_id=iid, wall_s=wall / K,
            meta={"k": k, "lanes": K}))
    return results
