"""stream_matmul — the paper's MM kernel, TPU-native.

INR-Arch's MM kernel buffers the streamed operand and emits outputs at an
initiation interval set by the DSP parallelism factor.  The TPU analogue is a
blocked matmul whose BlockSpec tiles play the role of the array-stream
blocks: A streams through VMEM tile-by-tile, the accumulator lives in VMEM
scratch (the "FIFO" between the MXU and the output stream), and the MXU tile
(bm x bn, multiples of 128) is the parallelism factor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, interpret_default


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def reduction_tile(bk: int, mm_parallel: int | None) -> int:
    """Map the HardwareConfig MM parallelism factor onto the Pallas reduction
    tile: the dataflow model's initiation interval is ceil(K / mm_parallel)
    and the TPU analogue reduces bk elements of K per grid step, so bk tracks
    mm_parallel (rounded up to the 8-lane sublane width)."""
    if mm_parallel is None:
        return bk
    return min(bk, max(8, -(-int(mm_parallel) // 8) * 8))


def stream_matmul(a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128,
                  bk: int = 128, out_dtype=None, interpret: bool | None = None,
                  mm_parallel: int | None = None):
    """C = A @ B with explicit VMEM tiling.  A: [M, K], B: [K, N].

    ``mm_parallel`` (from the segment's HardwareConfig stamp) sizes the
    reduction tile ``bk`` — the kernel-side meaning of the paper's MM
    parallelism factor."""
    if interpret is None:
        interpret = interpret_default()
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bk = reduction_tile(bk, mm_parallel)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    pad_m, pad_n, pad_k = (-M) % bm, (-N) % bn, (-K) % bk
    if pad_m or pad_k:
        a = jnp.pad(a, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        b = jnp.pad(b, ((0, pad_k), (0, pad_n)))
    Mp, Kp, Np = M + pad_m, K + pad_k, N + pad_n
    k_steps = Kp // bk

    out = pl.pallas_call(
        functools.partial(_mm_kernel, k_steps=k_steps),
        grid=(Mp // bm, Np // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out[:M, :N]
