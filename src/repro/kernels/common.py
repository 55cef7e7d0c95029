"""Shared Pallas utilities.

TPU is the TARGET; on this CPU container every kernel runs through
``interpret=True`` (Pallas executes the kernel body in Python), which the
tests use to validate against the pure-jnp oracles in ref.py.
"""

from __future__ import annotations

import jax


def interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


# Mosaic's default scoped-VMEM limit on a TPU v5e, and the most a kernel
# may ask for of the chip's 128 MiB
SCOPED_VMEM_DEFAULT = 16 << 20
SCOPED_VMEM_MAX = 100 << 20


def vmem_params(block_bytes: int):
    """Compiler params for a kernel whose pipelined operand and output
    blocks total ``block_bytes``: Pallas holds two buffers of each, plus
    room for the body's values.  None (the default limit) while that fits
    16 MiB; above, the limit the kernel needs (a 512-wide region's
    weights, their transposes and their gradient accumulators)."""
    need = 2 * block_bytes + (4 << 20)
    if need <= SCOPED_VMEM_DEFAULT:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(need, SCOPED_VMEM_MAX))
