"""Closed-loop tile serving through ``ServingEngine.serve``.

One client walks the ``grid_side`` x ``grid_side`` grid in
``tile_side`` x ``tile_side`` tiles in raster order (the seed picks the
first tile) and sends the next call when the last one has returned.  A
call asks for the gradient tower of ``order`` over the tile, or, when the
traffic names ``filters``, for every named filter over the tile as one
request each (a bank of closed-form filter heads compiled by
``filter_bank`` and routed with ``register_bank``).

End to end: ``rows_per_s``, request rows returned per second over the
whole window (a filter request counts its tile's rows).  Compared: every
call's outputs against the plain reference over the whole grid, as the
worst scaled error ``scaled_err``.
"""

from __future__ import annotations

import time

import jax

import program
import serving
from harness import Outcome, checks_of, stream_key


def _setup(run):
    """The weights, the grid, its tiles and the first tile, from the seed
    in one jitted call."""
    cell = run.cell
    cfg, tr = cell.config, cell.traffic
    ref = cell.reference()
    s = tr["tile_side"]
    origins = [(i, j) for i in range(0, tr["grid_side"], s)
               for j in range(0, tr["grid_side"], s)]

    @jax.jit
    def make(seed_key):
        g = serving.grid(tr["grid_side"], cfg["in_features"])
        return (ref.init_params(cfg, stream_key(seed_key, "weights")), g,
                tuple(serving.tile(g, i, j, s) for i, j in origins),
                jax.random.randint(stream_key(seed_key, "traffic"), (), 0,
                                   len(origins)))
    params, g, tiles, start = jax.block_until_ready(make(run.seed_key))
    return ref, params, g, origins, int(start), list(tiles)


def _engine(run, params, x_trace):
    from repro.core import pipeline
    from repro.serve import ServingEngine
    cfg, tr = run.cell.config, run.cell.traffic
    f = program.inr(cfg, params)
    engine = ServingEngine()
    filters = tr.get("filters")
    with run.phase("compile_s"):
        if filters:
            from repro.inr.filters import filter_bank
            bank = filter_bank(f, filters, x_trace, order=tr["order"],
                               alpha=tr["alpha"])
            engine.register_bank(filters, bank)
        else:
            cg = pipeline.compile_gradient(f, tr["order"], x_trace)
            engine.register("inr", cg)
    ids = list(filters) if filters else ["inr"]
    return engine, ids


def run(run, control=None):
    cfg, tr = run.cell.config, run.cell.traffic
    with run.phase("data_s"):
        ref, params, g, origins, start, tiles = _setup(run)
    s = tr["tile_side"]
    engine, ids = _engine(run, params, tiles[start][:tr["trace_rows"]])

    def call(k):
        out = engine.serve([(fid, tiles[k]) for fid in ids])
        return jax.block_until_ready(out)

    with run.phase("warmup_s"):
        for w in range(tr["warmup_calls"]):
            call((start + w) % len(tiles))

    tile_rows = s * s
    served, calls, failed, errors = [], 0, 0, []
    in_slice = [0]

    def work():
        return {"rows": in_slice[0] * tile_rows, "calls": in_slice[0]}

    k = start
    t0 = run.window_start()
    deadline = t0 + run.seconds
    while (now := time.perf_counter()) < deadline:
        run.slice_tick(now, work)
        try:
            with run.annotate("bench.serve_call"):
                out = call(k)
        except Exception as e:            # a call that raises has failed
            failed += len(ids)
            errors.append(repr(e))
        else:
            served.append(((*origins[k], s),
                           tuple(o[0] for o in out) if tr.get("filters")
                           else out[0]))
            calls += 1
            in_slice[0] += run.slicing
        k = (k + 1) % len(tiles)
    t1 = run.window_end()
    run.slice_close(work)

    del engine
    program.release()
    refs = serving.reference_grid(serving.reference_outputs(ref, cfg, tr),
                                  params, g, s)
    checks = checks_of({"scaled_err": serving.scaled_error(refs, served)},
                       run.cell.limits)
    notes = [f"{calls} calls of {len(ids)} x {tile_rows} rows"]
    if errors:
        notes.append(f"{failed} requests failed, last: {errors[-1]}")
    controls = {}
    if control is not None:
        grids = serving.reference_grid(
            serving.reference_outputs(ref, cfg, tr, control["precision"],
                                      control.get("dot")), params, g, s)
        # the reference in the program's place, one precision step down
        controls["control"] = {"scaled_err": serving.scaled_error(
            refs, [(q, serving.tile_of(grids, *q)) for q, _ in served])}
    rows = calls * len(ids) * tile_rows
    return Outcome(attempted=(calls * len(ids)) + failed, failed=failed,
                   values={"rows_per_s": rows / (t1 - t0)},
                   checks=checks, notes=notes, controls=controls)
