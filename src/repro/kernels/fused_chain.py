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

Comparisons (``eq`` ``ne`` ``lt`` ``le`` ``gt`` ``ge``) take the same second
input, or a scalar baked in as their operand, and leave a boolean mask in
the chain; ``("select", slot)`` is ``select_n(pred, false, true)`` with the
chain value at ``slot`` of those three and the other two as inputs.  A
mask made and consumed inside one kernel stays a VMEM value; one that
crosses a kernel boundary travels as float32 0 / 1 (``as_stream``).
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
    "log1p": jnp.log1p,
}
BINARY = {"mul", "add", "sub", "div", "max", "min"}
# ``ne`` is the unordered compare, true where either side is NaN, as XLA's
# ``ne``: Mosaic lowers ``!=`` as an ordered compare, so it is written as
# not-equal, which keeps ``isnan(x) = x != x`` a NaN test inside a kernel
COMPARE = {
    "eq": lambda a, b: a == b,
    "ne": lambda a, b: jnp.logical_not(a == b),
    "lt": lambda a, b: a < b, "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b, "ge": lambda a, b: a >= b,
}
SELECT = "select"


def n_extras(step) -> int:
    """Streamed inputs one chain step consumes: one per binary step and per
    comparison without a baked scalar, two per select."""
    op, operand = step
    if op in BINARY or (op in COMPARE and operand is None):
        return 1
    return 2 if op == SELECT else 0


def stream_dtype(dtype):
    """The dtype a value crosses HBM in: a boolean mask as float32 0 / 1
    (a select reads either form as its predicate)."""
    dtype = jnp.dtype(dtype)
    return jnp.dtype(jnp.float32) if dtype == jnp.bool_ else dtype


def as_stream(a):
    """A kernel operand in its ``stream_dtype``."""
    return a.astype(stream_dtype(a.dtype))


def eval_chain(h, chain, extras=()):
    """Apply a static chain of (op, operand) steps to ``h`` (float32).

    ``extras`` holds the streamed inputs the steps consume, in step order
    (``n_extras`` each).  This is the single evaluation rule both
    ``fused_chain`` and the region megakernel (``kernels/region.py``) trace
    into their bodies, so a chain computes bit-identically whether it runs
    standalone or fused into a region."""
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
        elif op in COMPARE:
            if operand is None:
                other = extras[ei]
                ei += 1
            else:
                other = operand
            h = COMPARE[op](h, other)
        elif op == SELECT:
            cases = [extras[ei], extras[ei + 1]]
            ei += 2
            cases.insert(operand, h)
            pred, lo, hi = cases
            h = jnp.where(pred, hi, lo)
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
                out_dtype=None, interpret: bool | None = None):
    """Apply `chain` to x: [R, C] streaming block_rows rows at a time.
    ``out_dtype`` (default ``x``'s) is the chain output's: a chain that
    ends on a comparison returns a boolean mask."""
    if interpret is None:
        interpret = interpret_default()
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    x = as_stream(x)
    extras = tuple(as_stream(e) for e in extras)
    R, C = x.shape
    br = min(block_rows, R)
    pad = (-R) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        extras = tuple(jnp.pad(e, ((0, pad), (0, 0))) for e in extras)
    Rp = R + pad
    n_extra = len(extras)
    assert sum(map(n_extras, chain)) == n_extra, (chain, n_extra)

    out = pl.pallas_call(
        functools.partial(_chain_kernel, chain=tuple(chain), n_extra=n_extra),
        grid=(Rp // br,),
        in_specs=[pl.BlockSpec((br, C), lambda i: (i, 0))] * (1 + n_extra),
        out_specs=pl.BlockSpec((br, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, C), stream_dtype(out_dtype)),
        interpret=interpret,
        name="fused_chain",
    )(x, *extras)
    return out[:R].astype(out_dtype)


# ---------------------------------------------------------------------------
# chain-spec builder: SegmentPlan StreamChain nodes -> a fused_chain call
# ---------------------------------------------------------------------------

# IR op -> kernel unary name
_IR_UNARY = {"Sin": "sin", "Cos": "cos", "Exp": "exp", "Tanh": "tanh",
             "Neg": "neg", "Abs": "abs", "Sigmoid": "sigmoid",
             "Log1p": "log1p"}
# IR op -> kernel binary name
_IR_BINARY = {"Mul": "mul", "Add": "add", "Sub": "sub", "Div": "div",
              "Maximum": "max", "Minimum": "min"}
# IR op -> kernel comparison, and the comparison with its sides swapped
_IR_COMPARE = {"Eq": "eq", "Ne": "ne", "Lt": "lt", "Le": "le", "Gt": "gt",
               "Ge": "ge"}
_SWAPPED = {"eq": "eq", "ne": "ne", "lt": "gt", "le": "ge", "gt": "lt",
            "ge": "le"}


@dataclass(frozen=True)
class ChainSpec:
    """A StreamChain segment lowered to one ``fused_chain`` invocation.

    ``steps`` is the kernel's static ``chain`` argument; ``extras`` holds the
    producer node ids of the steps' other operands, in order.
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
    the chain value in the left slot (the kernel computes ``h op other``).
    Comparisons take the chain value on either side (swapped to the left)
    and bake a size-1 Const in; a boolean Select takes it in any of its
    three slots."""
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
        elif n.op in _IR_COMPARE:
            if len(n.inputs) != 2:
                return None
            slot = 0 if n.inputs[0] == primary else 1
            op = _IR_COMPARE[n.op]
            op = op if slot == 0 else _SWAPPED[op]
            other = n.inputs[1 - slot]
            v = _scalar_const(g, other)
            if v is not None:
                steps.append((op, v))
            else:
                if other not in resident and g.nodes[other].shape != n.shape:
                    return None
                steps.append((op, None))
                extras.append(other)
        elif n.op == "Select":
            if (len(n.inputs) != 3
                    or g.nodes[n.inputs[0]].dtype != "bool"):
                return None                 # select_n over an integer index
            slot = n.inputs.index(primary)
            others = [i for k, i in enumerate(n.inputs) if k != slot]
            if prev is not None and primary in others:
                return None                 # the chain value twice
            if any(o not in resident and g.nodes[o].shape != n.shape
                   for o in others):
                return None
            steps.append((SELECT, slot))
            extras.extend(others)
        else:
            return None
        if prev is None:
            x = primary
        prev = nid
    return ChainSpec(x=x, steps=tuple(steps), extras=tuple(extras))
