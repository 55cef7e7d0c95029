"""region — a whole FusedRegion as ONE Pallas megakernel.

INR-Arch's speedup comes from connecting its stream kernels with on-chip
FIFO streams: an intermediate tensor flows from one PE to the next without
ever visiting DRAM.  The per-segment TPU execution loses exactly that — each
segment is its own ``pallas_call``, so every inter-segment tensor round-trips
a full ``(block, N)`` buffer through HBM.  This kernel is the TPU analogue of
the paper's FIFO-connected PE chain: it executes a whole region (a run of
StreamChain / MatMul / FusedMmAct segments, scheduled by ``core/regions.py``)
per grid step, holding every intermediate in VMEM values — one HBM read per
region input and one HBM write per region output, regardless of how many
segments the region fuses.

The region is described by a static ``RegionKernelSpec``: a tuple of steps
evaluated in order against a node-id -> value environment traced into the
kernel body.

  * ``("chain", out, x, chain_steps, extra_ids)`` — a StreamChain segment:
    ``fused_chain.eval_chain`` applied to ``env[x]`` (binary-step operands
    come from ``env[extra_ids[k]]``), bit-identical to the standalone kernel.
  * ``("mm", out, x, w, bias, w0, apply_sin)`` — a MatMul / FusedMmAct
    segment: ``env[x] @ w  [+ bias]  [-> sin(w0 *)]`` with the WHOLE weight
    resident in VMEM, the full K reduced in one MXU dot per row tile (the
    region trades the standalone kernel's ``bk`` reduction tiling for
    never materializing the MM input/output in HBM).
  * ``("concat", out, xs)`` — a last-axis Concat of region values (the
    gradient-feature assembly of a filter bank, DESIGN.md §9; a skip
    connection, whose operands may include a row-constant ``bcast_rows``
    row): row-wise, so it streams like any elementwise step; operand
    widths differ, so a concat step is never column-tiled.
  * ``("slice", out, x, lo, hi)`` — columns ``lo:hi`` of a region value
    (a Split, the transpose of a skip connection's Concat, or a column
    Slice): row-wise too, and never column-tiled.

Chain steps may compare and select (``fused_chain.COMPARE``, ``SELECT``):
their boolean masks are VMEM values of the kernel body.  A mask that
enters or leaves the region crosses HBM as float32 0 / 1, converted at the
call (``fused_chain.as_stream``), so no kernel operand is boolean.

The grid tiles ROWS (``bm`` from the HardwareConfig): every step's row-block
is independent, which is exactly why the paper can stream its graphs through
FIFOs.  On top of that, two locality refinements (DESIGN.md §7):

  * ``bcast_rows`` — row-constant resident chain extras enter the kernel as
    a single ``[1, C]`` VMEM row and broadcast inside the kernel, instead of
    the dispatcher materializing a ``[block, C]`` HBM operand per block.
    Bit-identical (jnp broadcasting against identical row values) and it
    removes ``block * C`` HBM bytes per block per extra.
  * ``tile_groups`` — COLUMN TILING inside a region: a contiguous run of
    wide (width > ``bn``) steps whose outputs feed only each other and one
    terminating "reducer" MM is evaluated ``bn`` columns at a time, the
    reducer accumulating ``acc += tile_j @ w[lo:hi, :]`` across tiles.  The
    wide intermediates then occupy ``bm * bn`` VMEM instead of ``bm * W``,
    so wide layers fit a tight budget instead of forcing a region cut.
    Non-reducer steps are bit-exact per tile; the reducer's K-reduction is
    reordered (tile-partial sums), so column-tiled regions guarantee
    allclose, not bit-exact, parity — the scheduler only tiles when the
    untiled region would NOT fit the budget.

For K-stacked multi-INR serving, ``region_call_stacked`` runs the same spec
over a ``[K, R, C]`` lane axis with the grid ordered ``(lane, row tile)``:
each lane's resident weights are one grid-block on the SLOW axis, so the
Pallas pipeline prefetches lane ``k+1``'s weights into VMEM while lane ``k``
computes its last row tile — region-level double buffering of the resident
weights that previously serialized the per-lane weight swap.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_default, vmem_params
from repro.kernels.fused_chain import as_stream, eval_chain, stream_dtype

# f32 dots at full precision: at the TPU default (one bf16 pass) every
# SIREN layer's w0 = 30 scale leaves gradients off by several percent
_HIGHEST = jax.lax.Precision.HIGHEST

CHAIN = "chain"
MM = "mm"
CONCAT = "concat"
SLICE = "slice"


@dataclass(frozen=True)
class TileGroup:
    """One column-tiled run inside a region's step program.

    ``members`` — node ids of the group's step outputs, in step order; every
    member step has output width ``width`` and its output is consumed only
    by later members or the reducer.
    ``reducer`` — node id of the terminating MM step's output: the MM whose
    streamed operand is the last member; its ``width``-long K reduction is
    carried across column tiles as a running accumulator.
    ``width`` / ``bn`` — the shared member width and the column tile; the
    group evaluates in ``ceil(width / bn)`` tiles (last tile ragged).
    """
    members: tuple[int, ...]
    reducer: int
    width: int
    bn: int

    @property
    def n_tiles(self) -> int:
        return -(-self.width // self.bn)


@dataclass(frozen=True)
class RegionKernelSpec:
    """Static description of one region megakernel.

    ``steps``         — evaluation program, in segment plan order (see module
                        docstring for the two step forms).
    ``stream_inputs`` — node ids read block-by-block from HBM, in kernel
                        argument order.  Includes resident chain extras the
                        dispatcher pre-broadcasts to block shape (only those
                        that do NOT qualify as ``bcast_rows``).
    ``bcast_rows``    — node ids of row-constant resident chain extras that
                        enter the kernel as one ``[1, C]`` VMEM row each and
                        broadcast inside the kernel.
    ``residents``     — node ids of whole-tensor VMEM operands (MM weights
                        and bias vectors), in kernel argument order.
    ``outputs``       — node ids written back to HBM, one out ref each.
    ``tile_groups``   — column-tiled runs of the step program (empty =
                        untiled; see ``TileGroup``).
    """
    steps: tuple
    stream_inputs: tuple[int, ...]
    residents: tuple[int, ...]
    outputs: tuple[int, ...]
    bcast_rows: tuple[int, ...] = ()
    tile_groups: tuple[TileGroup, ...] = ()

    @property
    def n_stream(self) -> int:
        return len(self.stream_inputs)


def _block_bytes(specs, shapes) -> int:
    """Bytes of one grid step's blocks of the given specs' arrays."""
    total = 0
    for spec, s in zip(specs, shapes):
        n = 1
        for d in spec.block_shape:
            n *= d
        total += n * jnp.dtype(s.dtype).itemsize
    return total


def _eval_mm(x, w, bias, w0, apply_sin):
    h = jnp.dot(x, w, precision=_HIGHEST, preferred_element_type=jnp.float32)
    if bias is not None:
        h = h + bias
    if apply_sin:
        h = jnp.sin(w0 * h)
    return h


def _as_2d(residents):
    """Residents as the kernel sees them: a 1-D bias enters as one ``[1, N]``
    row.  Mosaic tiles the last two dims of every operand, so a vmapped (or
    K-stacked) ``[K, N]`` bias would put the lane axis K where the compiler
    needs a tileable row dim; ``[K, 1, N]`` keeps ``(1, N)`` whole."""
    return [r.reshape(1, -1) if r.ndim < 2 else r for r in residents]


def _eval_group(env, res, group: TileGroup, member_steps, reducer_step):
    """Evaluate one column-tiled run: members ``bn`` columns at a time, the
    reducer accumulating partial K products across tiles.  ``lo:hi`` slices
    are static per tile (the loop unrolls at trace time)."""
    W, bn = group.width, group.bn
    members = set(group.members)
    _, r_out, r_x, r_w, r_bias, r_w0, r_sin = reducer_step
    wfull = res[r_w]
    acc = None
    for lo in range(0, W, bn):
        hi = min(W, lo + bn)
        tenv = {}

        def tile_val(nid):
            if nid in tenv:
                return tenv[nid]
            v = env[nid]
            # operands of a tiled step are either full-width (slice the
            # tile) or per-row scalars / [1,1] rows (broadcast whole)
            if v.shape[-1] == W:
                return v[..., lo:hi]
            return v

        for step in member_steps:
            if step[0] == CHAIN:
                _, out, x, chain_steps, extra_ids = step
                extras = [tile_val(e) for e in extra_ids]
                tenv[out] = eval_chain(tile_val(x), chain_steps, extras)
            else:
                _, out, x, w, bias, w0, apply_sin = step
                assert x not in members, "member MM lhs must be external"
                b = res[bias][:, lo:hi] if bias is not None else None
                tenv[out] = _eval_mm(env[x], res[w][:, lo:hi], b,
                                     w0, apply_sin)
        part = jnp.dot(tenv[r_x], wfull[lo:hi, :], precision=_HIGHEST,
                       preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    if r_bias is not None:
        acc = acc + res[r_bias]
    if r_sin:
        acc = jnp.sin(r_w0 * acc)
    env[r_out] = acc


def _eval_steps(env, res, spec: RegionKernelSpec):
    """Walk the step program, detouring through ``_eval_group`` for each
    column-tiled run (group steps are contiguous, reducer last)."""
    by_first = {}
    for g in spec.tile_groups:
        by_first[g.members[0]] = g
    i = 0
    steps = spec.steps
    while i < len(steps):
        step = steps[i]
        group = by_first.get(step[1])
        if group is not None:
            n = len(group.members)
            member_steps = steps[i:i + n]
            reducer_step = steps[i + n]
            assert reducer_step[1] == group.reducer, (group, reducer_step)
            _eval_group(env, res, group, member_steps, reducer_step)
            i += n + 1
            continue
        if step[0] == CHAIN:
            _, out, x, chain_steps, extra_ids = step
            extras = [env[e] for e in extra_ids]
            env[out] = eval_chain(env[x], chain_steps, extras)
        elif step[0] == MM:
            _, out, x, w, bias, w0, apply_sin = step
            env[out] = _eval_mm(env[x], res[w],
                                res[bias] if bias is not None else None,
                                w0, apply_sin)
        elif step[0] == CONCAT:
            _, out, xs = step
            vals = [env[x] for x in xs]
            # a row-const operand enters as one [1, C] row
            n = max(v.shape[0] for v in vals)
            env[out] = jnp.concatenate(
                [jnp.broadcast_to(v, (n, v.shape[1])) for v in vals], axis=-1)
        elif step[0] == SLICE:
            _, out, x, lo, hi = step
            env[out] = env[x][:, lo:hi]
        else:
            raise ValueError(f"region: unknown step kind {step[0]!r}")
        i += 1


def _region_kernel(*refs, spec: RegionKernelSpec, stacked: bool = False):
    ns = spec.n_stream
    nb = len(spec.bcast_rows)
    nr = len(spec.residents)

    def load(ref):
        v = ref[...]
        return v[0] if stacked else v

    env = {nid: load(refs[i]).astype(jnp.float32)
           for i, nid in enumerate(spec.stream_inputs)}
    for j, nid in enumerate(spec.bcast_rows):
        env[nid] = load(refs[ns + j]).astype(jnp.float32)
    res = {nid: load(refs[ns + nb + i]).astype(jnp.float32)
           for i, nid in enumerate(spec.residents)}
    _eval_steps(env, res, spec)
    out_refs = refs[ns + nb + nr:]
    for o_ref, nid in zip(out_refs, spec.outputs):
        v = env[nid]
        o_ref[...] = (v[None] if stacked else v).astype(o_ref.dtype)


def region_call(spec: RegionKernelSpec, stream, rows, residents, out_info, *,
                bm: int = 128, interpret: bool | None = None):
    """Execute one region over ``[R, C]`` streamed inputs.

    ``stream``    — arrays aligned with ``spec.stream_inputs`` (all [R, Ci]).
    ``rows``      — ``[1, Ci]`` arrays aligned with ``spec.bcast_rows``.
    ``residents`` — arrays aligned with ``spec.residents`` (whole tensors).
    ``out_info``  — ``(cols, dtype)`` per ``spec.outputs`` entry.

    Rows stream through the kernel ``bm`` at a time; intermediates live only
    as VMEM values inside one grid step.  Returns one array per output.
    """
    if interpret is None:
        interpret = interpret_default()
    assert len(stream) == len(spec.stream_inputs), (spec, len(stream))
    assert len(rows) == len(spec.bcast_rows), (spec, len(rows))
    stream = [as_stream(a) for a in stream]
    rows = [as_stream(a) for a in rows]
    residents = _as_2d(residents)
    R = stream[0].shape[0]
    br = min(bm, R)
    pad = (-R) % br
    if pad:
        stream = [jnp.pad(a, ((0, pad), (0, 0))) for a in stream]
    Rp = R + pad

    in_specs = [pl.BlockSpec((br, a.shape[1]), lambda i: (i, 0))
                for a in stream]
    in_specs += [pl.BlockSpec((1, a.shape[1]), lambda i: (0, 0))
                 for a in rows]
    in_specs += [pl.BlockSpec(r.shape, lambda i: (0, 0)) for r in residents]
    out_specs = [pl.BlockSpec((br, c), lambda i: (i, 0))
                 for c, _ in out_info]
    out_shape = [jax.ShapeDtypeStruct((Rp, c), stream_dtype(dt))
                 for c, dt in out_info]

    operands = (*stream, *rows, *residents)
    outs = pl.pallas_call(
        functools.partial(_region_kernel, spec=spec),
        grid=(Rp // br,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=vmem_params(
            _block_bytes(in_specs + out_specs, operands + tuple(out_shape))),
        name="region_fwd",
    )(*operands)
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    return tuple(o[:R].astype(dt) for o, (_, dt) in zip(outs, out_info))


def region_call_stacked(spec: RegionKernelSpec, stream, rows, residents,
                        out_info, *, bm: int = 128,
                        interpret: bool | None = None):
    """Execute one region over K stacked weight lanes in ONE ``pallas_call``.

    ``stream``    — ``[K, R, Ci]`` arrays aligned with ``spec.stream_inputs``.
    ``rows``      — ``[K, 1, Ci]`` arrays aligned with ``spec.bcast_rows``.
    ``residents`` — ``[K, ...]`` stacked whole tensors per ``spec.residents``.
    ``out_info``  — ``(cols, dtype)`` per output; returns ``[K, R, cols]``.

    The grid is ``(K, R/br)`` — lane on the SLOW axis, row tile on the fast
    axis — and every resident's block index depends only on the lane, so the
    Pallas pipeline DMAs lane ``k+1``'s weights into VMEM while lane ``k``
    computes its final row tile: the resident weight swap that serialized
    per-lane multi-INR region execution is overlapped with compute.
    """
    if interpret is None:
        interpret = interpret_default()
    assert len(stream) == len(spec.stream_inputs), (spec, len(stream))
    assert len(rows) == len(spec.bcast_rows), (spec, len(rows))
    stream = [as_stream(a) for a in stream]
    rows = [as_stream(a) for a in rows]
    K, R = stream[0].shape[0], stream[0].shape[1]
    residents = [r.reshape(K, 1, -1) if r.ndim < 3 else r for r in residents]
    br = min(bm, R)
    pad = (-R) % br
    if pad:
        stream = [jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in stream]
    Rp = R + pad

    in_specs = [pl.BlockSpec((1, br, a.shape[2]), lambda k, i: (k, i, 0))
                for a in stream]
    in_specs += [pl.BlockSpec((1, 1, a.shape[2]), lambda k, i: (k, 0, 0))
                 for a in rows]
    for r in residents:
        in_specs.append(pl.BlockSpec(
            (1,) + r.shape[1:],
            lambda k, i, nd=r.ndim - 1: (k,) + (0,) * nd))
    out_specs = [pl.BlockSpec((1, br, c), lambda k, i: (k, i, 0))
                 for c, _ in out_info]
    out_shape = [jax.ShapeDtypeStruct((K, Rp, c), stream_dtype(dt))
                 for c, dt in out_info]

    operands = (*stream, *rows, *residents)
    outs = pl.pallas_call(
        functools.partial(_region_kernel, spec=spec, stacked=True),
        grid=(K, Rp // br),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=vmem_params(
            _block_bytes(in_specs + out_specs, operands + tuple(out_shape))),
        name="region_fwd_stacked",
    )(*operands)
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    return tuple(o[:, :R].astype(dt) for o, (_, dt) in zip(outs, out_info))


# --------------------------------------------------------------------------
# fit path: differentiable region call with a VMEM-resident gradient
# accumulator (DESIGN.md §11)
# --------------------------------------------------------------------------

def _region_bwd_kernel(*refs, spec: RegionKernelSpec, n_out: int):
    """Backward megakernel for one region: per row tile, re-run the step
    program under ``jax.vjp`` and pull the output cotangents back to the
    region operands.  Per-row cotangents (``d_stream``) are written to their
    own ``(i, 0)``-mapped tile; per-PARAMETER cotangents (``d_rows`` /
    ``d_residents``) accumulate into ``(0, ...)``-mapped output refs that
    stay VMEM-resident across the whole row-tile grid — the xformers
    online-softmax idiom: the accumulator rides the carry, HBM sees exactly
    one flush per parameter, never a per-tile partial."""
    ns = spec.n_stream
    nb = len(spec.bcast_rows)
    nr = len(spec.residents)
    stream_vals = tuple(refs[i][...].astype(jnp.float32) for i in range(ns))
    row_vals = tuple(refs[ns + j][...].astype(jnp.float32)
                     for j in range(nb))
    res_vals = tuple(refs[ns + nb + i][...].astype(jnp.float32)
                     for i in range(nr))
    cot_vals = tuple(refs[ns + nb + nr + o][...].astype(jnp.float32)
                     for o in range(n_out))

    def fwd(stream_t, row_t, res_t):
        env = dict(zip(spec.stream_inputs, stream_t))
        env.update(zip(spec.bcast_rows, row_t))
        res = dict(zip(spec.residents, res_t))
        _eval_steps(env, res, spec)
        return tuple(env[o] for o in spec.outputs)

    _, pullback = jax.vjp(fwd, stream_vals, row_vals, res_vals)
    d_stream, d_rows, d_res = pullback(cot_vals)

    out_refs = refs[ns + nb + nr + n_out:]
    for j in range(ns):
        out_refs[j][...] = d_stream[j]
    first = pl.program_id(0) == 0
    for j, val in enumerate(tuple(d_rows) + tuple(d_res)):
        acc_ref = out_refs[ns + j]

        @pl.when(first)
        def _(acc_ref=acc_ref, val=val):
            acc_ref[...] = val

        @pl.when(jnp.logical_not(first))
        def _(acc_ref=acc_ref, val=val):
            acc_ref[...] += val


def _region_bwd_call(spec: RegionKernelSpec, stream, rows, residents, cots, *,
                     bm: int = 128, interpret: bool | None = None):
    """Dispatch the backward megakernel.  Padding rows get ZERO cotangents;
    the vjp is linear in the cotangent, so they contribute exactly zero to
    every accumulated parameter partial."""
    if interpret is None:
        interpret = interpret_default()
    ns, nb = len(stream), len(rows)
    shapes = [r.shape for r in residents]
    residents = _as_2d(residents)
    R = stream[0].shape[0]
    br = min(bm, R)
    pad = (-R) % br
    if pad:
        stream = [jnp.pad(a, ((0, pad), (0, 0))) for a in stream]
        cots = [jnp.pad(c, ((0, pad), (0, 0))) for c in cots]
    Rp = R + pad

    in_specs = [pl.BlockSpec((br, a.shape[1]), lambda i: (i, 0))
                for a in stream]
    in_specs += [pl.BlockSpec((1, a.shape[1]), lambda i: (0, 0))
                 for a in rows]
    in_specs += [pl.BlockSpec(r.shape, lambda i: (0, 0)) for r in residents]
    in_specs += [pl.BlockSpec((br, c.shape[1]), lambda i: (i, 0))
                 for c in cots]

    out_specs = [pl.BlockSpec((br, a.shape[1]), lambda i: (i, 0))
                 for a in stream]
    out_shape = [jax.ShapeDtypeStruct((Rp, a.shape[1]), jnp.float32)
                 for a in stream]
    for a in rows:
        out_specs.append(pl.BlockSpec((1, a.shape[1]), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, a.shape[1]), jnp.float32))
    for r in residents:
        out_specs.append(pl.BlockSpec(r.shape, lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(r.shape, jnp.float32))

    operands = (*stream, *rows, *residents, *cots)
    outs = pl.pallas_call(
        functools.partial(_region_bwd_kernel, spec=spec, n_out=len(cots)),
        grid=(Rp // br,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=vmem_params(
            _block_bytes(in_specs + out_specs, operands + tuple(out_shape))),
        name="region_bwd",
    )(*operands)
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    d_stream = tuple(o[:R] for o in outs[:ns])
    d_rows = tuple(outs[ns:ns + nb])
    d_res = tuple(o.reshape(s) for o, s in zip(outs[ns + nb:], shapes))
    return d_stream, d_rows, d_res


@functools.lru_cache(maxsize=None)
def region_grad_fn(spec: RegionKernelSpec, out_info: tuple, bm: int = 128,
                   interpret: bool | None = None):
    """Differentiable region call for the streamed fitting path.

    Returns a cached ``jax.custom_vjp`` callable over the flat operand tuple
    ``(*stream, *rows, *residents)``: the forward pass IS ``region_call``
    (bit-identical to serving), and the backward pass is ONE accumulating
    Pallas kernel (``_region_bwd_kernel``) that streams the same row tiles
    and keeps every per-parameter gradient partial in VMEM across the grid —
    one HBM flush per parameter per region call, instead of materializing a
    per-tile gradient tensor and reducing it afterwards."""
    ns = len(spec.stream_inputs)
    nb = len(spec.bcast_rows)
    # masks cross the custom_vjp as float32 0 / 1, so every operand and
    # output it differentiates is a float (no float0 cotangents inside)
    float_info = tuple((c, stream_dtype(dt)) for c, dt in out_info)

    @jax.custom_vjp
    def call(*ops):
        return region_call(spec, ops[:ns], ops[ns:ns + nb], ops[ns + nb:],
                           float_info, bm=bm, interpret=interpret)

    def call_fwd(*ops):
        return call(*ops), ops

    def call_bwd(ops, cots):
        d_stream, d_rows, d_res = _region_bwd_call(
            spec, list(ops[:ns]), list(ops[ns:ns + nb]),
            list(ops[ns + nb:]), list(cots), bm=bm, interpret=interpret)
        flat = d_stream + d_rows + d_res
        return tuple(d.astype(o.dtype) for d, o in zip(flat, ops))

    call.defvjp(call_fwd, call_bwd)

    def masked(*ops):
        outs = call(*(as_stream(o) for o in ops))
        return tuple(o.astype(dt) for o, (_, dt) in zip(outs, out_info))
    return masked
