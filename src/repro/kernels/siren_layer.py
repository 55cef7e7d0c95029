"""siren_layer — fused SIREN layer: y = sin(w0 * (x @ W + b)).

The INR-Arch dataflow overlaps the MM kernel with the downstream streaming
Sin kernel through a FIFO; on TPU the same fusion is one kernel: the sine is
applied to the VMEM accumulator tile before it is ever written to HBM, so the
intermediate (x@W+b) never exists as a materialized tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_default


def _siren_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
                  w0: float, apply_sin: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _emit():
        h = acc_ref[...] + b_ref[...].astype(jnp.float32)
        if apply_sin:
            h = jnp.sin(w0 * h)
        o_ref[...] = h.astype(o_ref.dtype)


def siren_layer(x: jax.Array, w: jax.Array, b: jax.Array, *, w0: float = 30.0,
                apply_sin: bool = True, bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool | None = None,
                mm_parallel: int | None = None):
    """x: [B, K], w: [K, N], b: [N] -> sin(w0 (x@w + b)) (or linear).

    ``mm_parallel`` (from the segment's HardwareConfig stamp) sizes the
    reduction tile ``bk``, as in ``stream_matmul``."""
    from repro.kernels.stream_matmul import reduction_tile

    if interpret is None:
        interpret = interpret_default()
    B, K = x.shape
    _, N = w.shape
    bk = reduction_tile(bk, mm_parallel)
    bm, bn, bk = min(bm, B), min(bn, N), min(bk, K)
    pm, pn, pk = (-B) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    if pn:
        b = jnp.pad(b, ((0, pn),))
    Bp, Kp, Np = B + pm, K + pk, N + pn
    k_steps = Kp // bk

    out = pl.pallas_call(
        functools.partial(_siren_kernel, k_steps=k_steps, w0=w0,
                          apply_sin=apply_sin),
        grid=(Bp // bm, Np // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bn,), lambda i, j, k: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w, b)
    return out[:B, :N]
