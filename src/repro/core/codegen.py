"""Code generation (paper Sec. 3.2.5, contribution 5).

The paper's compiler emits HLS C++ from the optimized graph + FIFO depths.
The TPU-native analogue emits a self-contained Python/JAX module from the
SegmentPlan (DESIGN.md §3):

  * ONE FUNCTION PER SEGMENT of the plan — a StreamChain fuses its whole op
    run into a single function (the XLA/Pallas fusion unit), MatMul /
    FusedMmAct / Buffering segments are the boundaries, mirroring the
    paper's stream-kernel library;
  * a `pipeline(consts, *inputs)` entry point whose per-block step wires the
    segment functions together in plan order (lax.map over blocks), with the
    optimized FIFO depths recorded as the block double-buffer factor;
  * the emitted source is returned as a string AND can be exec-loaded, so the
    artifact is inspectable exactly like generated HLS code.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.config import HardwareConfig
from repro.core.graph import ComputeGraph
from repro.core.segment import (SegmentPlan, build_segment_plan,
                                segment_dispatch, _p)

_OP_EXPR = {
    "Mm": "jnp.matmul({0}, {1}, precision='highest')",
    "T": "{0}.T",
    "Sin": "jnp.sin({0})",
    "Cos": "jnp.cos({0})",
    "Mul": "{0} * {1}",
    "Add": "{0} + {1}",
    "Sub": "{0} - {1}",
    "Div": "{0} / {1}",
    "Neg": "-{0}",
    "Exp": "jnp.exp({0})",
    "Log": "jnp.log({0})",
    "Tanh": "jnp.tanh({0})",
    "Rsqrt": "jax.lax.rsqrt({0})",
    "Sqrt": "jnp.sqrt({0})",
    "Abs": "jnp.abs({0})",
    "Sigmoid": "jax.nn.sigmoid({0})",
    "Maximum": "jnp.maximum({0}, {1})",
    "Minimum": "jnp.minimum({0}, {1})",
    "Select": "jnp.where({0}, {2}, {1})",    # select_n(pred, false, true)
    "Log1p": "jnp.log1p({0})",
    "Eq": "{0} == {1}",
    "Ne": "{0} != {1}",
    "Lt": "{0} < {1}",
    "Le": "{0} <= {1}",
    "Gt": "{0} > {1}",
    "Ge": "{0} >= {1}",
    "Identity": "{0}",
    "Pow": "{0} ** {1}",
}


def _expr(node, args):
    op = node.op
    if op in _OP_EXPR:
        return _OP_EXPR[op].format(*args)
    if op == "Permute":
        return f"jnp.transpose({args[0]}, {tuple(_p(node, 'permutation'))})"
    if op == "IntPow":
        return f"jax.lax.integer_pow({args[0]}, {_p(node, 'y')})"
    if op == "Convert":
        return f"{args[0]}.astype('{node.dtype}')"
    if op == "Broadcast":
        return (f"jax.lax.broadcast_in_dim({args[0]}, _bshape({node.shape!r}, "
                f"{args[0]}), {tuple(_p(node, 'broadcast_dimensions', ()))})")
    if op == "Reshape":
        return f"{args[0]}.reshape(_bshape({node.shape!r}, {args[0]}))"
    if op == "Sum":
        return f"jnp.sum({args[0]}, axis={_p(node, 'axes')})"
    if op == "Slice":
        start = list(_p(node, "start_indices"))
        limit = list(_p(node, "limit_indices"))
        strides = list(_p(node, "strides") or [1] * len(start))
        return (f"jax.lax.slice({args[0]}, [0 if _i == 0 else _s for _i, _s in "
                f"enumerate({start})], [{args[0]}.shape[0] if _i == 0 else _l "
                f"for _i, _l in enumerate({limit})], {strides})")
    if op == "Pad":
        pc = list(_p(node, "padding_config"))
        return f"jax.lax.pad({args[0]}, {args[1]}.astype({args[0]}.dtype), {pc})"
    if op == "Concat":
        return f"jnp.concatenate([{', '.join(args)}], axis={_p(node, 'dimension')})"
    raise NotImplementedError(f"codegen: {op}")


def _seg_fn_name(seg) -> str:
    return f"seg{seg.id}_{seg.kind.lower()}"


def _emit_nodes(L, g: ComputeGraph, plan: SegmentPlan, nodes, node_set,
                blk_ref: str, B: int):
    """Emit one ``v{nid} = ...`` line per IR node (shared by the segment and
    region emitters)."""
    for nid in nodes:
        n = g.nodes[nid]
        args = []
        for i in n.inputs:
            if i in node_set or i not in plan.resident:
                args.append(f"v{i}")
                continue
            a = f"_r[{i}]"
            if i in plan.rowconst and g.nodes[i].shape[:1] == (B,):
                # row-const residents shrink to one block; weights stay whole
                a = f"{a}[:{blk_ref}.shape[0]]"
            args.append(a)
        L.append(f"    v{nid} = {_expr(n, args)}")


def _emit_segment(L, g: ComputeGraph, plan: SegmentPlan, seg, B: int):
    """One function per segment: streams in, one tensor out."""
    params = ", ".join(["_r"] + [f"v{i}" for i in seg.stream_inputs])
    ops = "+".join(g.nodes[n].op for n in seg.nodes)
    kernel = segment_dispatch(plan, seg)
    L.append(f"def {_seg_fn_name(seg)}({params}):")
    L.append(f'    """{seg.kind}: {ops} -> n{seg.output} '
             f'[dispatch: {kernel}]."""')
    blk_ref = f"v{seg.stream_inputs[0]}"
    _emit_nodes(L, g, plan, seg.nodes, set(seg.nodes), blk_ref, B)
    L.append(f"    return v{seg.output}")
    L.append("")


def _region_fn_name(region) -> str:
    return f"region{region.id}"


def _emit_region(L, g: ComputeGraph, plan: SegmentPlan, region, B: int):
    """One function per FUSED region: the megakernel's source analogue —
    every member segment inlined, intermediates never leave the function,
    streams in, the region's outputs out."""
    params = ", ".join(["_r"] + [f"v{i}" for i in region.stream_inputs])
    segs = "+".join(f"s{s}" for s in region.segments)
    tiles = region.meta.get("col_tiles", 1)
    tiled = (f", column-tiled x{tiles} (reduction carried across bn tiles)"
             if tiles > 1 else "")
    L.append(f"def {_region_fn_name(region)}({params}):")
    L.append(f'    """FusedRegion {segs}: one megakernel, intermediates '
             f'in VMEM{tiled} [dispatch: region]."""')
    blk_ref = f"v{region.stream_inputs[0]}"
    nodes = [n for sid in region.segments
             for n in plan.segments[sid].nodes]
    _emit_nodes(L, g, plan, nodes, set(nodes), blk_ref, B)
    outs = ", ".join(f"v{o}" for o in region.outputs)
    L.append(f"    return ({outs},)")
    L.append("")


def emit_python(g: ComputeGraph, *, block: int | None = None,
                name: str = "generated",
                depths: dict | None = None,
                plan: SegmentPlan | None = None,
                config: HardwareConfig | None = None,
                region_plan=None) -> str:
    """Emit a Python/JAX module implementing the optimized graph, one
    function per execution unit: fused regions (when the config enables the
    region scheduler) become one function each — the source analogue of the
    region megakernel — and every remaining segment keeps its own function.
    The region structure follows the SCHEDULE (``config.fuse_regions``),
    independent of ``use_pallas``: an interpreted artifact's source still
    shows the fusion the plan describes, just as it always named the Pallas
    kernels it did not dispatch (see core/regions.py).  The emitted source
    records the HardwareConfig it was compiled for (``HARDWARE_CONFIG``),
    the way the paper's generated HLS bakes in its configured hardware
    parameters."""
    if plan is None:
        plan = build_segment_plan(g, config=config)
    if config is None:
        config = plan.config
    if block is None:
        block = config.block if config is not None else 8
    if region_plan is None and config is not None and config.fuse_regions:
        from repro.core.regions import build_region_plan
        region_plan = build_region_plan(plan, config)
    order = g.topo_order()
    B = plan.batch
    consts = [nid for nid in order
              if nid in plan.resident and g.nodes[nid].op == "Const"]

    L: list[str] = []
    L.append(f'"""Auto-generated by repro.core.codegen — INR-Arch pipeline.')
    L.append(f'graph: {len(g.nodes)} nodes / {g.n_edges} edges;')
    L.append(f'plan: {len(plan.segments)} segments {plan.counts_by_kind()};')
    if region_plan is not None and region_plan.fused_regions():
        c = region_plan.counts()
        L.append(f'regions: {c["regions"]} units, {c["fused"]} fused '
                 f'covering {c["segments_fused"]} segments;')
    if config is not None:
        L.append(f'hardware config: {config.describe()}')
    if depths is not None:
        L.append(f'optimized FIFO sum-depth: {sum(depths.values())} blocks')
    L.append('"""')
    L.append("import jax")
    L.append("import jax.numpy as jnp")
    L.append("")
    L.append("BLOCK = %d" % block)
    L.append("BATCH = %d" % B)
    if config is not None:
        L.append(f"HARDWARE_CONFIG = {config.as_dict()!r}")
    L.append("")
    L.append("def _bshape(shape, ref):")
    L.append("    # rewrite static batch dim to the incoming block's batch")
    L.append("    if shape and ref.ndim and shape[0] == BATCH:")
    L.append("        return (ref.shape[0],) + tuple(shape[1:])")
    L.append("    return tuple(shape)")
    L.append("")

    # residents
    L.append("def residents(consts):")
    L.append('    """Precompute const-derived tensors (weights stay on-chip)."""')
    for i, nid in enumerate(consts):
        L.append(f"    v{nid} = consts[{i}]")
    for nid in order:
        if nid not in plan.resident or g.nodes[nid].op == "Const":
            continue
        n = g.nodes[nid]
        args = [f"v{i}" for i in n.inputs]
        L.append(f"    v{nid} = {_expr(n, args)}")
    rlist = plan.resident_order()
    L.append(f"    return ({', '.join('v%d' % i for i in rlist)},)")
    L.append("")
    L.append(f"_RESIDENT_IDS = {tuple(rlist)}")
    L.append("")

    # one function per execution unit — the stream-kernel library for this
    # graph: fused regions inline their member segments (DESIGN.md §7)
    units = (region_plan.units() if region_plan is not None
             else [("seg", s) for s in plan.segments])
    for kind, u in units:
        if kind == "region":
            _emit_region(L, g, plan, u, B)
        else:
            _emit_segment(L, g, plan, u, B)

    # per-block wiring: calls unit functions in plan (topological) order.
    # resident (const-derived) outputs never stream — pipeline() returns
    # them straight from resident memory, as the dataflow mapping models
    streamed_outs = [o for o in g.outputs if o not in plan.resident]
    L.append("def pipeline_step(res, *xblk):")
    L.append('    """One pipeline step: wire every unit over one block."""')
    L.append("    _r = dict(zip(_RESIDENT_IDS, res))")
    for nid in plan.inputs:
        L.append(f"    v{nid} = xblk[{_p(g.nodes[nid], 'idx')}]")

    for kind, u in units:
        if kind == "region":
            args = ", ".join(["_r"] + [f"v{i}" for i in u.stream_inputs])
            outs_l = ", ".join(f"v{o}" for o in u.outputs)
            L.append(f"    {outs_l}, = {_region_fn_name(u)}({args})"
                     if len(u.outputs) == 1 else
                     f"    {outs_l} = {_region_fn_name(u)}({args})")
        else:
            args = ", ".join(["_r"] + [f"v{i}" for i in u.stream_inputs])
            L.append(f"    v{u.output} = {_seg_fn_name(u)}({args})")
    outs = ", ".join(f"v{o}" for o in streamed_outs)
    L.append(f"    return ({outs},)")
    L.append("")

    L.append("def pipeline(consts, *inputs):")
    L.append('    """Streaming execution: blocks flow through the segments."""')
    L.append("    res = residents(consts)")
    if streamed_outs:
        L.append("    nb = BATCH // BLOCK")
        L.append("    xb = tuple(x.reshape(nb, BLOCK, *x.shape[1:]) "
                 "for x in inputs)")
        L.append("    outs = jax.lax.map(lambda b: pipeline_step(res, *b), xb)")
        L.append("    outs = [o.reshape(BATCH, *o.shape[2:]) for o in outs]")
    final = []
    k = 0
    for o in g.outputs:
        if o in plan.resident:
            final.append(f"res[{rlist.index(o)}]")
        else:
            final.append(f"outs[{k}]")
            k += 1
    L.append(f"    return ({', '.join(final)},)")
    L.append("")
    return "\n".join(L)


def load_generated(src: str):
    """exec the emitted module; returns (pipeline_fn, consts_extractor)."""
    ns: dict = {}
    exec(compile(src, "<inr-arch-codegen>", "exec"), ns)
    return ns["pipeline"], ns


def graph_consts(g: ComputeGraph, plan: SegmentPlan | None = None):
    resident_ids = (plan.resident if plan is not None
                    else build_segment_plan(g).resident)
    return [jnp.asarray(g.nodes[nid].const) for nid in g.topo_order()
            if nid in resident_ids and g.nodes[nid].op == "Const"]
