"""One streamed fitting job through ``compile_fit`` and ``fit``.

The INR is fitted to the normals of a signed distance field: ``points``
points drawn uniformly in [-1, 1]^D, each with the unit gradient of an
analytic SDF made from the seed (the union of ``spheres.count`` spheres,
centres uniform in ``spheres.centre``, radii uniform in
``spheres.radius``), under ``GradMSE`` at order 1.  Each step takes
``batch_rows`` rows, the program's per-epoch shuffle of its row blocks;
the optimizer is Adam as ``adam`` states it (no weight decay, no
clipping, a rate that stays constant over the job).  The benchmark's own
computations (the points, the reference) take ``piece_rows`` rows at a
time, so that their transient memory stays below the program's.

Set-up compiles the fit and runs a warm-up job of ``reference_steps``
steps from the seed's weights, through the same ``fit`` call and the same
compiled ``CompiledFit``; its steady time per step, read from the
program's ``fit_steps`` counter, sizes the window job.  The window job
starts from the same weights and schedule; its start (the ``fit`` call,
its preparation and re-trace, its first step) is set-up, and the window
runs from its first counted step to its result (``step_window``).  A
traced run's job traces a slice of ``trace_steps`` whole steps, from
counter to counter, starting the profiler ``trace_lead_s`` before the
slice.

End to end: ``fit_rows_per_s``, the steps counted in the window times
``batch_rows`` over the window's time, and ``peak_hbm_mb``, the device's
peak memory after the window.

After the window the plain reference follows the same rows through every
step of both jobs, and these numbers are compared, each with the limit
the cell's limits file gives it:

- ``grad1_gap``: the first step's gradient, by the worst leaf: the gap
  between the norms of the program's and the reference's leaf, relative
  to the larger of that leaf's and the median leaf's reference norm.  The
  program's is read through its public ``CompiledFit.value_and_grad`` on
  the first step's rows, since ``fit`` hands back no optimizer state;
- ``steps_gap``: the steps asked of the window job less the fewest it
  reports taking (its counter and its list of step losses);
- ``curve_head_gap``: the worst relative gap of the window job's step
  losses over its first ``curve_steps`` steps (two epochs, so the first
  reshuffle is inside);
- ``final_loss_gap``: the relative gap of the loss over all points, by
  the reference, at the window job's final parameters and at the
  reference's after the same steps.

The first step's loss and the parameters' change over the warm-up's
steps are read with the control but not compared (PERF.md says why).
Leaves whose first reference gradient is under a thousandth of the median
leaf's are left out of the change: Adam moves them by round-off alone.

The control (``control=``) puts the reference in the program's place one
precision step down, and two faults planted in it: each step over half
its rows, and a step that leaves the state unchanged.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import program
from harness import Outcome, checks_of, stream_key
from step_window import run_job, steps_for


def pieces(n: int, rows: int) -> int:
    """The fewest equal pieces of ``n`` rows with at most about ``rows``
    rows each (the next count that divides ``n``)."""
    k = max(1, -(-n // rows))
    while n % k:
        k += 1
    return k


def make_data(key, n: int, dims: int, spheres: dict, rows: int):
    """Points and the unit normals of the nearest sphere's surface, built
    in pieces of about ``rows`` points (traceable)."""
    kp, kc, kr = jax.random.split(key, 3)
    m, k = spheres["count"], pieces(n, rows)
    lo, hi = spheres["centre"]
    c = jax.random.uniform(kc, (m, dims), jnp.float32, lo, hi)
    lo, hi = spheres["radius"]
    r = jax.random.uniform(kr, (m,), jnp.float32, lo, hi)

    def piece(kq):
        pts = jax.random.uniform(kq, (n // k, dims), jnp.float32, -1.0, 1.0)
        d = pts[:, None, :] - c[None]
        near = jnp.argmin(jnp.linalg.norm(d, axis=-1) - r[None], axis=-1)
        v = jnp.take_along_axis(d, near[:, None, None], 1)[:, 0]
        return pts, v / jnp.maximum(
            jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-12)
    pts, nrm = jax.lax.map(piece, jax.random.split(kp, k))
    return pts.reshape(n, dims), nrm.reshape(n, dims)


def chunk_indices(key, n_blocks: int, chunk_blocks: int, steps: int):
    """The row blocks each step of ``fit`` takes: a fresh permutation of
    the blocks per epoch, consumed ``chunk_blocks`` at a time."""
    out, perm, pos, k = [], None, 0, key
    for _ in range(steps):
        if perm is None or pos + chunk_blocks > n_blocks:
            k, sub = jax.random.split(k)
            perm = np.asarray(jax.random.permutation(sub, n_blocks))
            pos = 0
        out.append(perm[pos:pos + chunk_blocks])
        pos += chunk_blocks
    return out


def reference_step(ref, cfg, hp, block, rows, precision="highest", dot=None,
                   frozen=False):
    """One jitted Adam step of the plain reference over the rows of the
    blocks ``idx``, its loss and gradient the mean of equal pieces of
    about ``rows`` rows: ``(params, state, pts, tgt, idx, i) -> (params,
    state, loss, grad)``.  ``frozen`` leaves the state unchanged (a
    planted fault)."""
    def loss(p, x, t):
        return ref.grad_mse(cfg, p, x, t, precision, dot)

    def step(p, state, pts, tgt, idx, i):
        at = (idx[:, None] * block + jnp.arange(block)[None]).reshape(-1)
        k = pieces(at.shape[0], rows)
        x = pts[at].reshape(k, -1, pts.shape[-1])
        t = tgt[at].reshape(k, -1, tgt.shape[-1])

        def body(carry, inp):
            return jax.tree.map(jnp.add, carry,
                                jax.value_and_grad(loss)(p, *inp)), None
        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (lv, g), _ = jax.lax.scan(body, zero, (x, t))
        lv, g = jax.tree.map(lambda a: a / k, (lv, g))
        if not frozen:
            p, state = ref.adam_step(hp, p, g, state, i)
        return p, state, lv, g
    return jax.jit(step)


def follow(step, params, pts, tgt, blocks, keep: int):
    """The reference through ``blocks``: step losses, the first gradient,
    the parameters after ``keep`` steps and after the last."""
    state = (jax.tree.map(jnp.zeros_like, params),
             jax.tree.map(jnp.zeros_like, params))
    p, losses, first, kept = params, [], None, params
    for i, idx in enumerate(blocks):
        p, state, lv, g = step(p, state, pts, tgt, jnp.asarray(idx), i)
        losses.append(lv)
        first = g if first is None else first
        if i + 1 == keep:
            kept = p
    return [float(x) for x in jax.device_get(losses)], first, kept, p


def full_loss(ref, cfg, pts, tgt, rows: int):
    """The loss over every point by the reference, in equal pieces of
    about ``rows`` rows."""
    f = jax.jit(lambda p, x, t: ref.grad_mse(cfg, p, x, t))
    n = pts.shape[0]
    size = n // pieces(n, rows)

    def loss(params) -> float:
        parts = [f(params, pts[k:k + size], tgt[k:k + size])
                 for k in range(0, n, size)]
        return float(jnp.mean(jnp.stack(parts)))
    return loss


def loss1_gap(firsts, want) -> float:
    """Worst relative gap of the first step's loss (at the seed's weights,
    before any update) against the reference's."""
    return max(abs(l - want[0]) / abs(want[0]) for l in firsts)


def _norms(tree) -> list[float]:
    return [float(jnp.linalg.norm(x)) for x in jax.tree_util.tree_leaves(tree)]


def dparam_gaps(p0, p_prog, p_ref, g_ref) -> list[float]:
    """Per leaf that counts, the gap of the change's norm, program against
    reference, relative to the larger of that leaf's and the median leaf's
    reference change."""
    d_prog = _norms(jax.tree.map(lambda a, b: a - b, p_prog, p0))
    d_ref = _norms(jax.tree.map(lambda a, b: a - b, p_ref, p0))
    g = _norms(g_ref)
    keep = [i for i in range(len(g)) if g[i] >= 1e-3 * float(np.median(g))]
    med = float(np.median([d_ref[i] for i in keep]))
    return [abs(d_prog[i] - d_ref[i]) / max(d_ref[i], med) for i in keep]


def grad1_gap(g, g_ref) -> float:
    """Worst leaf gap of the first gradient's norm, program against
    reference."""
    n, r = _norms(g), _norms(g_ref)
    med = float(np.median(r))
    return max(abs(a - b) / max(b, med) for a, b in zip(n, r))


def flips(p, p_ref, lr: float) -> list[int]:
    """Per leaf, the elements whose change parts from the reference's by
    half a step of ``lr`` or more: an Adam step that went the other way."""
    return [int(jnp.sum(jnp.abs(a - b) >= 0.5 * lr)) for a, b in zip(
        jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(p_ref))]


class Reference:
    """What the plain reference read over the rows both jobs took, and
    the numbers of any run that took them, against it."""

    def __init__(self, p0, want, g_ref, kept, final, loss, head):
        self.p0, self.want, self.g_ref, self.head = p0, want, g_ref, head
        self.kept, self.loss = kept, loss
        self.final_loss = loss(final)

    def numbers(self, *, grad1, losses, final, steps: int,
                taken: int) -> dict:
        inf = float("inf")
        n = min(len(losses), len(self.want), self.head)
        return {
            "grad1_gap": grad1_gap(grad1, self.g_ref),
            "steps_gap": float(abs(steps - taken)),
            "curve_head_gap": max((abs(a - b) / abs(b) for a, b in
                                   zip(losses[:n], self.want[:n])),
                                  default=inf),
            "final_loss_gap": (abs(self.loss(final) - self.final_loss)
                               / self.final_loss) if final is not None
            else inf}

    def look(self, *, firsts, kept, lr: float) -> dict:
        """What is read but not compared (PERF.md says why): the first
        step's loss, and per leaf the change over the warm-up."""
        return {"loss1_gap": loss1_gap(firsts, self.want),
                "dparam_gaps": dparam_gaps(self.p0, kept, self.kept,
                                           self.g_ref),
                "flips": flips(kept, self.kept, lr)}


def run(run, control=None):
    from repro.fit import GradMSE, compile_fit, fit
    from repro.optim.adam import AdamWConfig
    cfg, tr = run.cell.config, run.cell.traffic
    ref = run.cell.reference()
    B, n_ref, rows = tr["batch_rows"], tr["reference_steps"], tr["piece_rows"]

    @jax.jit
    def make(seed_key):
        return (ref.init_params(cfg, stream_key(seed_key, "weights")),
                *make_data(stream_key(seed_key, "points"), tr["points"],
                           cfg["in_features"], tr["spheres"], rows))

    with run.phase("data_s"):
        params, pts, tgt = jax.block_until_ready(make(run.seed_key))
    run.read_memory("weights and data")
    hp = tr["adam"]
    adam = AdamWConfig(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                       weight_decay=0.0, clip_norm=0.0, warmup_steps=0,
                       total_steps=hp["constant_rate_steps"])
    key = run.key("schedule")

    with run.phase("compile_s"):
        cf = compile_fit(program.inr(cfg, params), GradMSE(), tr["order"],
                         pts[:tr["trace_rows"]], params=params)

    def job(steps):
        res = fit(cf, pts, tgt, steps=steps, params=params, adam=adam,
                  key=key, batch_rows=B)
        jax.block_until_ready(res.params)
        return res

    counter = _step_counter()
    with run.phase("warmup_s"):
        warm = run_job(lambda: job(n_ref), counter)
    run.read_memory("after the warm-up")
    # a traced job: its first step, the step in which the profiler
    # starts, the slice's steps, and one more, so that the watcher sees the
    # slice's last step counted while the job still runs
    traced = tr["trace_steps"]
    steps = 3 + traced if run.trace else steps_for(run.seconds, warm)

    def opened(t):
        run.window_start(t)
        if run.trace:
            # the profiler's stop takes time in proportion to the device
            # operations traced (about a million a step), so it starts
            # shortly before the step that opens the slice
            time.sleep(max(0.0, warm.step_s - tr["trace_lead_s"]))
            run.profiler_start()

    slice_at = []

    def slice_steps(n):
        # the slice runs from one counted step to the one ``traced`` later
        if not slice_at:
            slice_at.append(n)
            run.slice_start()
            return False
        if n - slice_at[0] < traced:
            return False
        run.slice_stop(rows=(n - slice_at[0]) * B, calls=n - slice_at[0])
        return True

    failed, errors, res, timed = 0, [], None, None
    t_job = time.perf_counter()
    try:
        with run.annotate("bench.fit_job"):
            timed = run_job(lambda: job(steps), counter,
                            on_open=opened,
                            on_count=slice_steps if run.trace else None)
        res = timed.result
    except Exception as e:                # a job that raises has failed
        failed = 1
        errors.append(repr(e))
    if timed is None or not timed.opened:
        # no window opened inside the job: the window is the whole job
        run.window_start(timed.t_call if timed else t_job)
    run.window_end(timed.t_close if timed else None)
    run.slice_close(lambda: {"rows": 0, "calls": 0})
    if timed is not None and timed.opened:
        run.host["job_start_s"] = timed.start_s
    taken = (min(timed.n_close - timed.n_call, len(res.losses))
             if res is not None else 0)

    block = _block_rows()
    blocks = chunk_indices(key, tr["points"] // block, B // block, steps)
    prog_grad = first_gradient(cf, params, pts, tgt, blocks[0], block)
    del cf
    program.release()
    firsts = [warm.result.losses[0]] + ([res.losses[0]] if res else [])
    mine = dict(grad1=prog_grad, losses=res.losses if res else [],
                final=res.params if res else None, steps=steps, taken=taken)
    del res

    def reference(blocks, precision="highest", dot=None, **kw):
        step = reference_step(ref, cfg, hp, block, rows, precision, dot, **kw)
        return follow(step, params, pts, tgt, blocks, n_ref)

    want, g_ref, kept, final = reference(blocks)
    truth = Reference(params, want, g_ref, kept, final,
                      full_loss(ref, cfg, pts, tgt, rows), tr["curve_steps"])
    checks = checks_of(truth.numbers(**mine), run.cell.limits)
    notes = []
    if timed is not None and timed.note:
        notes.append(f"window: {timed.note}")
    notes.append(
        f"{steps} steps of {B} rows asked, {taken} taken, "
        f"{timed.steps if timed else 0} in the window; warm-up job "
        f"{warm.steps} steps timed at {warm.step_s:.4f}s a step; first "
        f"losses of the warm-up job {warm.result.losses} and the reference "
        f"{want[:n_ref]}; first loss of the window job {firsts[1:]}; final "
        f"loss {truth.final_loss!r} by the reference")
    if errors:
        notes.append(f"the job failed: {errors[-1]}")
    controls, stats = {}, {}
    if control is not None:
        # the reference in the program's place: one precision step down,
        # and with two faults planted in it
        variants = {"control": dict(precision=control["precision"],
                                    dot=control.get("dot")),
                    "half_batch": dict(half=True),
                    "unchanged_state": dict(frozen=True)}
        for name, kw in variants.items():
            half = kw.pop("half", False)
            got = reference([b[:len(b) // 2] for b in blocks] if half
                            else blocks, **kw)
            controls[name] = truth.numbers(
                grad1=got[1], losses=got[0], final=got[3], steps=steps,
                taken=steps)
            stats[name] = truth.look(firsts=got[0][:1], kept=got[2],
                                     lr=hp["lr"])
        # a witness that runs no program code: the reference's first
        # gradient summed block by block, in the order a step sums it
        seq = reference_step(ref, cfg, hp, block, block, frozen=True)
        g_seq = seq(params, None, pts, tgt, jnp.asarray(blocks[0]), 0)[3]
        stats["grad1"] = {"program": grad1_gap(prog_grad, g_ref),
                          "blockwise_reference": grad1_gap(g_seq, g_ref)}
        stats["program"] = dict(
            truth.look(firsts=firsts, kept=warm.result.params, lr=hp["lr"]),
            curve_gaps=[abs(a - b) / abs(b) for a, b in zip(
                mine["losses"][:truth.head], want[:truth.head])])
    values = {"fit_rows_per_s": (timed.steps * B / timed.seconds
                                 if timed and not failed else 0.0),
              "peak_hbm_mb": (run.memory_peak_bytes or 0) / 1e6}
    return Outcome(attempted=1, failed=failed, values=values, checks=checks,
                   notes=notes, controls=controls, stats=stats)


def first_gradient(cf, params, pts, tgt, idx, block):
    """The program's own gradient of the first step's rows, through its
    public ``CompiledFit.value_and_grad`` (the same block loss as a step,
    streamed), kept on the host."""
    at = (np.asarray(idx)[:, None] * block
          + np.arange(block)[None]).reshape(-1)
    _, g = cf.value_and_grad(params, pts[at], tgt[at])
    return jax.device_get(g)


def _step_counter():
    """The program's own count of optimizer steps taken."""
    from repro.obs.metrics import REGISTRY
    metric = REGISTRY.get("fit_steps")
    return lambda: int(metric.value())


def _block_rows() -> int:
    """Rows per block of the program's fit (its default configuration)."""
    from repro.core.config import DEFAULT_CONFIG
    return DEFAULT_CONFIG.block
