"""fused_chain — a streaming-kernel segment as one Pallas kernel.

INR-Arch's library composes 1:1 stream kernels (Sin, Cos, Mul-by-const, ...)
through FIFOs; the codegen's TPU analogue fuses a contiguous segment of
streaming ops into ONE kernel that reads a block from HBM, applies the whole
chain in VMEM/VREGs, and writes one block back — the entire segment costs a
single round-trip of memory traffic regardless of chain length.

The chain is a static list of (op, operand) tuples evaluated inside the
kernel body at trace time:
    [("sin", None), ("scale", 30.0), ("add_row", bias), ("mul", other)]
`add_row`/`mul` take a second streamed input of matching block shape.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_default

UNARY = {
    "sin": jnp.sin, "cos": jnp.cos, "exp": jnp.exp, "tanh": jnp.tanh,
    "neg": lambda x: -x, "abs": jnp.abs, "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu, "square": jnp.square,
}
BINARY = {"mul", "add", "sub", "div", "max", "min"}


def eval_chain(h, chain, extras=()):
    """Apply a static chain of (op, operand) steps to ``h`` (float32).

    ``extras`` holds one float32 array per BINARY step, in step order.  This
    is the single evaluation rule both ``fused_chain`` and the region
    megakernel (``kernels/region.py``) trace into their bodies, so a chain
    computes bit-identically whether it runs standalone or fused into a
    region."""
    ei = 0
    for op, operand in chain:
        if op in UNARY:
            h = UNARY[op](h)
        elif op == "scale":
            h = h * operand
        elif op == "offset":
            h = h + operand
        elif op in BINARY:
            other = extras[ei]
            ei += 1
            if op == "mul":
                h = h * other
            elif op == "add":
                h = h + other
            elif op == "sub":
                h = h - other
            elif op == "max":
                h = jnp.maximum(h, other)
            elif op == "min":
                h = jnp.minimum(h, other)
            else:
                h = h / other
        else:
            raise ValueError(f"fused_chain: unknown op {op}")
    return h


def _chain_kernel(*refs, chain, n_extra):
    x_ref = refs[0]
    extra = refs[1:1 + n_extra]
    o_ref = refs[1 + n_extra]
    h = x_ref[...].astype(jnp.float32)
    extras = [e[...].astype(jnp.float32) for e in extra]
    o_ref[...] = eval_chain(h, chain, extras).astype(o_ref.dtype)


def fused_chain(x: jax.Array, chain, extras=(), *, block_rows: int = 256,
                interpret: bool | None = None):
    """Apply `chain` to x: [R, C] streaming block_rows rows at a time."""
    if interpret is None:
        interpret = interpret_default()
    R, C = x.shape
    br = min(block_rows, R)
    pad = (-R) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        extras = tuple(jnp.pad(e, ((0, pad), (0, 0))) for e in extras)
    Rp = R + pad
    n_extra = len(extras)
    n_bin = sum(1 for op, _ in chain if op in BINARY)
    assert n_bin == n_extra, (n_bin, n_extra)

    out = pl.pallas_call(
        functools.partial(_chain_kernel, chain=tuple(chain), n_extra=n_extra),
        grid=(Rp // br,),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0))] * (1 + n_extra),
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, C), x.dtype),
        interpret=interpret,
        name="fused_chain",
    )(x, *extras)
    return out[:R]


# ---------------------------------------------------------------------------
# chain-spec builder: SegmentPlan StreamChain nodes -> a fused_chain call
# ---------------------------------------------------------------------------

# IR op -> kernel unary name
_IR_UNARY = {"Sin": "sin", "Cos": "cos", "Exp": "exp", "Tanh": "tanh",
             "Neg": "neg", "Abs": "abs", "Sigmoid": "sigmoid"}
# IR op -> kernel binary name
_IR_BINARY = {"Mul": "mul", "Add": "add", "Sub": "sub", "Div": "div",
              "Maximum": "max", "Minimum": "min"}


@dataclass(frozen=True)
class ChainSpec:
    """A StreamChain segment lowered to one ``fused_chain`` invocation.

    ``steps`` is the kernel's static ``chain`` argument; ``extras`` holds the
    producer node id feeding each binary step's second operand, in order.
    ``x`` is the primary streamed input the chain starts from."""
    x: int
    steps: tuple
    extras: tuple[int, ...]


def _scalar_const(g, nid):
    """Static float of a size-1 Const node, else None (local duplicate of
    core.segment.scalar_const_value — kernels must not import core)."""
    n = g.nodes.get(nid)
    if n is None or n.op != "Const" or n.const is None:
        return None
    if int(np.prod(n.shape)) != 1:
        return None
    return float(np.ravel(n.const)[0])


def build_chain_spec(g, node_ids, *, resident):
    """Lower an ordered run of elementwise IR nodes to a ChainSpec, or None
    when any node is not expressible by the fused_chain kernel (the caller
    then interprets the segment node-by-node).

    Expressible ops: the _IR_UNARY map, IntPow(y=2) as square, and
    Mul/Add/Sub/Div — with a size-1 Const operand baked in as scale/offset,
    otherwise as a binary step streaming the second operand.  Sub/Div require
    the chain value in the left slot (the kernel computes ``h op other``)."""
    if not node_ids:
        return None
    steps: list = []
    extras: list[int] = []
    prev = None
    x = None
    for nid in node_ids:
        n = g.nodes[nid]
        if prev is None:
            streamed = [i for i in n.inputs if i not in resident]
            primary = streamed[0] if streamed else (n.inputs[0] if n.inputs
                                                    else None)
            if primary is None:
                return None
        else:
            primary = prev
            if primary not in n.inputs:
                return None
        if n.op in _IR_UNARY:
            steps.append((_IR_UNARY[n.op], None))
        elif n.op == "IntPow":
            if dict(n.params).get("y") != 2:
                return None
            steps.append(("square", None))
        elif n.op in _IR_BINARY:
            if len(n.inputs) != 2:
                return None
            slot = 0 if n.inputs[0] == primary else 1
            other = n.inputs[1 - slot]
            v = _scalar_const(g, other)
            if v is not None and n.op == "Mul":
                steps.append(("scale", v))
            elif v is not None and n.op == "Add":
                steps.append(("offset", v))
            elif v is not None and n.op == "Sub" and slot == 0:
                steps.append(("offset", -v))
            elif v is not None and n.op == "Div" and slot == 0 and v != 0.0:
                steps.append(("scale", 1.0 / v))
            else:
                if n.op in ("Sub", "Div") and slot != 0:
                    return None             # other - h / other / h: no kernel op
                if other not in resident and g.nodes[other].shape != n.shape:
                    return None             # streamed extra must match blocks
                steps.append((_IR_BINARY[n.op], None))
                extras.append(other)
        else:
            return None
        if prev is None:
            x = primary
        prev = nid
    return ChainSpec(x=x, steps=tuple(steps), extras=tuple(extras))
