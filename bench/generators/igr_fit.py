"""One streamed IGR fitting job through ``compile_fit`` and ``fit``.

The INR is fitted as IGR fits a surface (Gropp et al., ICML 2020): to
``surface_points`` points on the surface of an analytic union of
``spheres.count`` spheres made from the seed (centres uniform in
``spheres.centre``, radii in ``spheres.radius``; a point that falls inside
another sphere is redrawn), with their outward normals, under ``IGRLoss``
at order 1.  The eikonal term is read at one local sample per surface
point, the point plus N(0, sigma^2) per coordinate, and at
``global_points`` samples uniform in [-global_sigma, global_sigma]^3.
sigma is the 50th-nearest-neighbour distance of a uniform sample of the
union's surface, taken analytically (the configuration's ``assumed``
says why).  Rows are laid out surface, local, global, each class whole
8-row blocks, so the fit's shuffle of blocks mixes them in IGR's 8 : 8 : 1
on average.  Each step takes ``batch_rows`` rows; Adam as ``adam``
states it.

Set-up, window, the traced slice and every number compared are those of
``fit_job`` (its docstring says how each is read); only the loss differs:
the reference follows the steps with ``ref.igr_loss``.  The set-up also
reads, from the program's counters, what its ``compile_fit`` left to the
per-node interpreter and why its region scheduler cut, into the run's
host readings (``compile_interp_nodes``, ``compile_region_cuts``).
"""

from __future__ import annotations

import math
import time
from pathlib import Path

import jax
import jax.numpy as jnp

import program
from harness import Outcome, checks_of, load_module, stream_key
from step_window import run_job, steps_for

_fit_job = load_module(Path(__file__).with_name("fit_job.py"),
                       "bench_generator_fit_job")
Reference, chunk_indices = _fit_job.Reference, _fit_job.chunk_indices
first_gradient, follow = _fit_job.first_gradient, _fit_job.follow
grad1_gap, pieces = _fit_job.grad1_gap, _fit_job.pieces


def sphere_surface(key, n: int, dims: int, spheres: dict, oversample: int,
                   rows: int):
    """``n`` points uniform on the surface of a union of spheres, their
    outward normals, the surface's area and the fewest points any piece
    kept.  Candidates are drawn on the spheres in proportion to their
    areas, ``oversample`` per point, and one that another sphere holds is
    dropped; each piece of about ``rows`` candidates keeps its first
    ``n / pieces`` survivors, and the share that survive estimates the
    area (traceable)."""
    kc, kr, kp = jax.random.split(key, 3)
    m = spheres["count"]
    lo, hi = spheres["centre"]
    c = jax.random.uniform(kc, (m, dims), jnp.float32, lo, hi)
    lo, hi = spheres["radius"]
    r = jax.random.uniform(kr, (m,), jnp.float32, lo, hi)
    area = 4.0 * math.pi * r ** 2
    k = pieces(n, max(1, rows // oversample))
    per = n // k

    def piece(kq):
        ks, kd = jax.random.split(kq)
        which = jax.random.choice(ks, m, (per * oversample,),
                                  p=area / jnp.sum(area))
        u = jax.random.normal(kd, (per * oversample, dims), jnp.float32)
        u = u / jnp.linalg.norm(u, axis=-1, keepdims=True)
        pts = c[which] + r[which][:, None] * u
        dist = jnp.linalg.norm(pts[:, None, :] - c[None], axis=-1)
        inside = (dist < r[None]) & (jnp.arange(m)[None] != which[:, None])
        kept = ~jnp.any(inside, axis=-1)
        at = jnp.nonzero(kept, size=per)[0]
        return pts[at], u[at], jnp.sum(kept)
    pts, nrm, n_kept = jax.lax.map(piece, jax.random.split(kp, k))
    share = jnp.sum(n_kept) / (n * oversample)
    return (pts.reshape(n, dims), nrm.reshape(n, dims),
            jnp.sum(area) * share, jnp.min(n_kept) * k)


def make_data(key, cfg: dict, tr: dict):
    """The rows and targets of the job: surface points with their normals,
    then their local samples and the global samples, whose targets are
    zero (eikonal-only rows)."""
    dims, n = cfg["in_features"], tr["surface_points"]
    ks, kl, kg = jax.random.split(key, 3)
    pts, nrm, area, n_kept = sphere_surface(
        ks, n, dims, tr["spheres"], tr["candidates_per_point"],
        tr["piece_rows"])
    k = cfg["local_sigma_neighbours"]
    sigma = jnp.sqrt(k * area / (math.pi * n))
    local = pts + sigma * jax.random.normal(kl, pts.shape, jnp.float32)
    g = tr["global_sigma"]
    glob = jax.random.uniform(kg, (tr["global_points"], dims), jnp.float32,
                              -g, g)
    x = jnp.concatenate([pts, local, glob])
    t = jnp.concatenate([nrm, jnp.zeros((n + glob.shape[0], dims),
                                        jnp.float32)])
    return x, t, sigma, n_kept


def reference_step(ref, cfg, hp, block, rows, precision="highest", dot=None,
                   frozen=False):
    """``fit_job.reference_step`` under IGR's loss: one jitted Adam step of
    the plain reference over the rows of the blocks ``idx``, its loss and
    gradient the mean of equal pieces of about ``rows`` rows."""
    def loss(p, x, t):
        return ref.igr_loss(cfg, p, x, t, precision, dot)

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


def full_loss(ref, cfg, pts, tgt, rows: int):
    """IGR's loss over every row by the reference, in equal pieces of
    about ``rows`` rows."""
    f = jax.jit(lambda p, x, t: ref.igr_loss(cfg, p, x, t))
    n = pts.shape[0]
    size = n // pieces(n, rows)

    def loss(params) -> float:
        parts = [f(params, pts[k:k + size], tgt[k:k + size])
                 for k in range(0, n, size)]
        return float(jnp.mean(jnp.stack(parts)))
    return loss


def _counter(name: str, **labels) -> float | None:
    """A program counter's value, or None where the program has none."""
    from repro.obs.metrics import REGISTRY
    metric = REGISTRY.get(name)
    return None if metric is None else metric.value(**labels)


def _cuts() -> dict:
    from repro.obs.metrics import REGISTRY
    metric = REGISTRY.get("compile_region_cuts")
    if metric is None:
        return {}
    return {reason: metric.value(reason=reason)
            for reason in ("vmem", "op", "forced")}


def run(run, control=None):
    from repro.fit import IGRLoss, compile_fit, fit
    from repro.optim.adam import AdamWConfig
    cfg, tr = run.cell.config, run.cell.traffic
    ref = run.cell.reference()
    B, n_ref, rows = tr["batch_rows"], tr["reference_steps"], tr["piece_rows"]

    @jax.jit
    def make(seed_key):
        return (ref.init_params(cfg, stream_key(seed_key, "weights")),
                *make_data(stream_key(seed_key, "points"), cfg, tr))

    with run.phase("data_s"):
        params, pts, tgt, sigma, n_kept = jax.block_until_ready(
            make(run.seed_key))
    if int(n_kept) < tr["surface_points"]:
        raise ValueError("a piece of candidates kept too few surface points "
                         "for its share: raise candidates_per_point")
    run.read_memory("weights and data")
    hp = tr["adam"]
    adam = AdamWConfig(lr=hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                       weight_decay=0.0, clip_norm=0.0, warmup_steps=0,
                       total_steps=hp["constant_rate_steps"])
    key = run.key("schedule")
    if (cfg["normals_lambda"], cfg["grad_lambda"]) != (1, 0.1):
        raise ValueError("the program's IGRLoss weighs its terms as IGR "
                         "publishes: normals_lambda 1, grad_lambda 0.1")
    a, e = ref.loss_weights(cfg)
    loss = IGRLoss(surface_weight=a, eikonal_weight=e)

    interp0, cuts0 = _counter("compile_interp_nodes", path="fit"), _cuts()
    with run.phase("compile_s"):
        cf = compile_fit(program.inr(cfg, params), loss, tr["order"],
                         pts[:tr["trace_rows"]], params=params)
    interp1, cuts1 = _counter("compile_interp_nodes", path="fit"), _cuts()
    if interp1 is not None:               # registered at the first compile
        run.host["compile_interp_nodes"] = interp1 - (interp0 or 0.0)
    run.host["compile_region_cuts"] = {k: v - cuts0.get(k, 0.0)
                                       for k, v in cuts1.items()}

    def job(steps):
        res = fit(cf, pts, tgt, steps=steps, params=params, adam=adam,
                  key=key, batch_rows=B)
        jax.block_until_ready(res.params)
        return res

    counter = _fit_job._step_counter()
    with run.phase("warmup_s"):
        warm = run_job(lambda: job(n_ref), counter)
    run.read_memory("after the warm-up")
    traced = tr["trace_steps"]
    steps = 3 + traced if run.trace else steps_for(run.seconds, warm)

    def opened(t):
        run.window_start(t)
        if run.trace:
            time.sleep(max(0.0, warm.step_s - tr["trace_lead_s"]))
            run.profiler_start()

    slice_at = []

    def slice_steps(n):
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
    except Exception as err:              # a job that raises has failed
        failed = 1
        errors.append(repr(err))
    if timed is None or not timed.opened:
        run.window_start(timed.t_call if timed else t_job)
    run.window_end(timed.t_close if timed else None)
    run.slice_close(lambda: {"rows": 0, "calls": 0})
    if timed is not None and timed.opened:
        run.host["job_start_s"] = timed.start_s
    taken = (min(timed.n_close - timed.n_call, len(res.losses))
             if res is not None else 0)

    block = _fit_job._block_rows()
    blocks = chunk_indices(key, pts.shape[0] // block, B // block, steps)
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
        f"{warm.steps} steps timed at {warm.step_s:.4f}s a step; local "
        f"sigma {float(sigma)!r}; compile_fit left "
        f"{run.host.get('compile_interp_nodes')} nodes to the interpreter, "
        f"region cuts {run.host['compile_region_cuts']}; first losses of "
        f"the warm-up job {warm.result.losses} and the reference "
        f"{want[:n_ref]}; first loss of the window job {firsts[1:]}; final "
        f"loss {truth.final_loss!r} by the reference")
    if errors:
        notes.append(f"the job failed: {errors[-1]}")
    controls, stats = {}, {}
    if control is not None:
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
