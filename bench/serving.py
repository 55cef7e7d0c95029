"""What the serving generators share: the coordinate grid, square tile
requests on it, and the comparison of served tiles with the reference.

A request is a square tile of the ``G x G`` grid over [-1, 1]^2, given by
its origin ``(i, j)`` and side ``s``: ``s * s`` coordinate rows in
row-major order.  The reference is computed once over the whole grid, in
tiles, after the window; every served request is then compared with its
slice of it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def grid(side: int, dims: int = 2):
    """[side, side, 2] coordinates over [-1, 1]^2 (row = first axis)."""
    if dims != 2:
        raise ValueError("tile requests are written for 2-D inputs")
    lin = jnp.linspace(-1.0, 1.0, side, dtype=jnp.float32)
    xx, yy = jnp.meshgrid(lin, lin, indexing="ij")
    return jnp.stack([xx, yy], -1)


@partial(jax.jit, static_argnums=(3,))
def tile(g, i, j, s: int):
    """The s x s tile at origin (i, j) as [s * s, D] rows."""
    t = jax.lax.dynamic_slice(g, (i, j, 0), (s, s, g.shape[-1]))
    return t.reshape(s * s, g.shape[-1])


def reference_outputs(ref, cfg: dict, tr: dict,
                      precision: str = "highest", dot=None):
    """(params, rows) -> what one request of the traffic returns, by the
    plain reference: the gradient tower of ``order``, or, when the traffic
    names ``filters``, each filter head over the tower."""
    filters = tr.get("filters")

    def outputs(params, x):
        with jax.default_matmul_precision(precision):
            outs = ref.tower(cfg, params, x, tr["order"], precision, dot)
            if filters:
                return tuple(ref.filter_head(n, outs, cfg, tr["alpha"])
                             for n in filters)
            return outs
    return outputs


def reference_grid(outputs_fn, params, g, tile_side: int):
    """``outputs_fn(params, rows) -> tuple of [rows, c]`` evaluated over
    the whole grid tile by tile; returns one [G, G, c] array per output.
    The weights are an argument, so the compiled reference serves every
    seed."""
    G = g.shape[0]
    fn = jax.jit(outputs_fn)
    rows = []
    for i in range(0, G, tile_side):
        cols = []
        for j in range(0, G, tile_side):
            outs = fn(params, tile(g, i, j, tile_side))
            cols.append([o.reshape(tile_side, tile_side, -1) for o in outs])
        rows.append([jnp.concatenate(parts, 1) for parts in zip(*cols)])
    return tuple(jnp.concatenate(parts, 0) for parts in zip(*rows))


def tile_of(grids, i: int, j: int, s: int):
    """The outputs of request (i, j, s) read off [G, G, c] grids."""
    return tuple(r[i:i + s, j:j + s].reshape(s * s, -1) for r in grids)


@partial(jax.jit, static_argnums=(4,))
def _tile_gaps(refs, outs, i, j, s: int):
    gaps = []
    for r, o in zip(refs, outs):
        want = jax.lax.dynamic_slice(r, (i, j, 0), (s, s, r.shape[-1]))
        gaps.append(jnp.max(jnp.abs(o.reshape(want.shape) - want)))
    return jnp.stack(gaps)


def scaled_error(refs, served) -> float:
    """Worst scaled error over every served request and output:
    max |out - ref| over the request's rows, divided by max |ref| of that
    output over the whole grid.  ``served`` is [((i, j, s), outs), ...]."""
    if not served:
        return float("inf")
    scale = jnp.stack([jnp.max(jnp.abs(r)) for r in refs])
    gaps = jnp.stack([_tile_gaps(refs, tuple(outs), i, j, s)
                      for (i, j, s), outs in served])
    worst = jnp.max(gaps, axis=0) / jnp.maximum(scale, 1e-30)
    return float(jnp.max(worst))
