"""Graph executors: buffered reference vs. block-streaming (paper Sec. 3.1).

* ``reference_executor`` evaluates the ComputeGraph op-by-op in topological
  order, materializing every intermediate — the CPU/GPU-style buffered
  execution the paper compares against.

* ``streaming_executor`` is the TPU-native analogue of the INR-Arch dataflow
  architecture, driven by the SegmentPlan (DESIGN.md §3): const-derived
  tensors are PRECOMPUTED RESIDENTS (the paper keeps weights on-chip); the
  batch dim is split into blocks that flow segment-by-segment through the
  plan under ``lax.map``, each segment dispatching to its Pallas stream
  kernel (fused_chain / stream_matmul / siren_layer) or to the per-node
  interpreter as a reference fallback.  Since the CompiledGradient layer
  (DESIGN.md §4) it is a thin wrapper: compile-or-hit, then apply.

Both are built from the same IR, so they agree numerically (tests assert it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.graph import ComputeGraph, Node
from repro.core.segment import (SegmentPlan, build_segment_plan,
                                classify_residents, _p)


_COMPARE = {"Eq": jnp.equal, "Ne": jnp.not_equal, "Lt": jnp.less,
            "Le": jnp.less_equal, "Gt": jnp.greater, "Ge": jnp.greater_equal}


def _eval_node(node: Node, args, block_b: int | None = None):
    """Evaluate one IR node given operand values."""
    op = node.op
    shape = node.shape
    if block_b is not None and len(shape) > 0:
        shape = (block_b, *shape[1:])
    if op == "Mm":
        return jnp.matmul(args[0], args[1],
                          precision=jax.lax.Precision.HIGHEST)
    if op == "T":
        return args[0].T
    if op == "Permute":
        return jnp.transpose(args[0], _p(node, "permutation"))
    if op == "Sin":
        return jnp.sin(args[0])
    if op == "Cos":
        return jnp.cos(args[0])
    if op == "Mul":
        return args[0] * args[1]
    if op == "Add":
        return args[0] + args[1]
    if op == "Sub":
        return args[0] - args[1]
    if op == "Div":
        return args[0] / args[1]
    if op == "Neg":
        return -args[0]
    if op == "Exp":
        return jnp.exp(args[0])
    if op == "Log":
        return jnp.log(args[0])
    if op == "Tanh":
        return jnp.tanh(args[0])
    if op == "Rsqrt":
        return jax.lax.rsqrt(args[0])
    if op == "Sqrt":
        return jnp.sqrt(args[0])
    if op == "Abs":
        return jnp.abs(args[0])
    if op == "Sign":
        return jnp.sign(args[0])
    if op == "Sigmoid":
        return jax.nn.sigmoid(args[0])
    if op == "Erf":
        return jax.lax.erf(args[0])
    if op == "IntPow":
        return jax.lax.integer_pow(args[0], _p(node, "y"))
    if op == "Pow":
        return args[0] ** args[1]
    if op == "Maximum":
        return jnp.maximum(args[0], args[1])
    if op == "Minimum":
        return jnp.minimum(args[0], args[1])
    if op == "Log1p":
        return jnp.log1p(args[0])
    if op in _COMPARE:
        return _COMPARE[op](args[0], args[1])
    if op == "Select":
        # select_n(pred, *cases): a boolean pred picks cases[1] where true
        if len(args) == 3 and jnp.result_type(args[0]) == jnp.bool_:
            return jnp.where(args[0], args[2], args[1])
        return jax.lax.select_n(*args)
    if op == "Convert":
        return args[0].astype(node.dtype)
    if op == "Identity":
        return args[0]
    if op == "Broadcast":
        bdims = _p(node, "broadcast_dimensions", ())
        out = args[0]
        if block_b is not None and 0 in bdims and out.ndim and out.shape[0] != 1:
            # operand carries the batch dim: expand around it
            pass
        return jax.lax.broadcast_in_dim(out, shape, bdims)
    if op == "Reshape":
        return args[0].reshape(shape)
    if op == "Sum":
        return jnp.sum(args[0], axis=_p(node, "axes"))
    if op == "Max":
        return jnp.max(args[0], axis=_p(node, "axes"))
    if op == "Concat":
        return jnp.concatenate(args, axis=_p(node, "dimension"))
    if op == "Slice":
        start = list(_p(node, "start_indices"))
        limit = list(_p(node, "limit_indices"))
        strides = _p(node, "strides") or [1] * len(start)
        if block_b is not None and args[0].ndim:
            # batch dim is never sliced in a streamable graph
            start[0], limit[0] = 0, args[0].shape[0]
        return jax.lax.slice(args[0], start, limit, list(strides))
    if op == "Pad":
        cfg_pad = list(_p(node, "padding_config"))
        return jax.lax.pad(args[0], args[1].astype(args[0].dtype) if hasattr(args[1],'astype') else args[1], cfg_pad)
    if op == "Iota":
        return jax.lax.broadcasted_iota(node.dtype, shape, _p(node, "dimension", 0))
    raise NotImplementedError(f"executor: op {op} ({node.params})")


def reference_executor(g: ComputeGraph):
    """Returns f(*inputs) evaluating the graph op-by-op (buffered)."""
    order = g.topo_order()

    def f(*inputs):
        env: dict[int, jax.Array] = {}
        for nid in order:
            n = g.nodes[nid]
            if n.op == "Input":
                env[nid] = inputs[_p(n, "idx")]
            elif n.op == "Const":
                env[nid] = jnp.asarray(n.const)
            else:
                env[nid] = _eval_node(n, [env[i] for i in n.inputs])
        return tuple(env[o] for o in g.outputs)
    return f


def check_streamable(g: ComputeGraph) -> bool:
    """Every stream-carried tensor must keep the batch dim in axis 0."""
    resident, streamed = classify_residents(g)
    inputs = [n for n in g.nodes.values() if n.op == "Input"]
    if not inputs:
        return False
    B = inputs[0].shape[0] if inputs[0].shape else None
    if B is None:
        return False
    for nid in streamed:
        n = g.nodes[nid]
        if n.op == "Input":
            if not n.shape or n.shape[0] != B:
                return False
            continue
        if not n.shape or n.shape[0] != B:
            return False
        # batch dim must not be contracted/permuted away
        if n.op == "Mm":
            lhs = g.nodes[n.inputs[0]]
            if lhs.id not in resident and lhs.shape[0] != B:
                return False
        if n.op in ("T",):
            return False                      # transposing batch out of axis 0
        if n.op == "Permute":
            perm = _p(n, "permutation")
            if perm and perm[0] != 0:
                return False
        if n.op == "Slice":
            start = _p(n, "start_indices")
            inp = g.nodes[n.inputs[0]]
            if start and (start[0] != 0 or _p(n, "limit_indices")[0] != inp.shape[0]):
                return False
        if n.op == "Pad":
            pc = _p(n, "padding_config")
            if pc and tuple(pc[0]) != (0, 0, 0):
                return False
    return True


def _resident_val(plan: SegmentPlan, res_env, i: int, block: int, B: int):
    a = res_env[i]
    # broadcast-row-constant residents shrink to one block; weights
    # (even if dim0 == B) stay whole
    if i in plan.rowconst and a.ndim and a.shape[:1] == (B,):
        a = a[:block]
    return a


def _run_segment(plan: SegmentPlan, seg, kernel: str, env, res_env,
                 block: int, B: int):
    """Execute one segment on one block; returns the segment's output."""
    g = plan.graph
    cfg = plan.config
    bm = cfg.bm if cfg is not None else 128
    bn = cfg.bn if cfg is not None else 128

    def val(i):
        if i in plan.resident:
            return _resident_val(plan, res_env, i, block, B)
        return env[i]

    if kernel == "stream_matmul":
        from repro.kernels.stream_matmul import stream_matmul
        mm = g.nodes[seg.nodes[0]]
        return stream_matmul(env[mm.inputs[0]], res_env[mm.inputs[1]],
                             bm=bm, bn=bn,
                             mm_parallel=seg.meta.get("mm_parallel"))

    if kernel == "siren_layer":
        from repro.kernels.siren_layer import siren_layer
        mm = g.nodes[seg.meta["mm"]]
        x = env[mm.inputs[0]]
        w = res_env[mm.inputs[1]]
        if seg.meta["bias"] is None:
            b = jnp.zeros((w.shape[1],), x.dtype)
        else:
            # bias is (N,), (1, N), or a row-const (B, N): one row is the vector
            b = res_env[seg.meta["bias"]]
            b = b[0] if b.ndim == 2 else b
        return siren_layer(x, w, b, w0=seg.meta["w0"],
                           apply_sin=seg.meta["apply_sin"], bm=bm, bn=bn,
                           mm_parallel=seg.meta.get("mm_parallel"))

    if kernel == "fused_chain":
        from repro.kernels.fused_chain import fused_chain
        spec = seg.meta["chain"]
        x = val(spec.x)
        extras = []
        for e in spec.extras:
            a = val(e)
            extras.append(a if a.shape == x.shape
                          else jnp.broadcast_to(a, x.shape))
        return fused_chain(x, spec.steps, tuple(extras), block_rows=bm,
                           out_dtype=g.nodes[seg.output].dtype)

    # reference fallback: interpret the segment node-by-node
    local: dict[int, jax.Array] = {}
    node_set = set(seg.nodes)
    for nid in seg.nodes:
        n = g.nodes[nid]
        args = [local[i] if i in node_set else val(i) for i in n.inputs]
        local[nid] = _eval_node(n, args, block_b=block)
    return local[seg.output]


def _run_region(plan: SegmentPlan, region, env, res_env, block: int, B: int):
    """Execute one FusedRegion on one block through the region megakernel
    (``kernels.region``): intermediates stay in VMEM — one HBM read per
    region input, one write per region output.  Region outputs are assigned
    into ``env``."""
    from repro.kernels.region import region_call
    g = plan.graph
    spec = region.spec
    cfg = plan.config

    stream = [env[nid] for nid in region.stream_inputs]
    n_rows = stream[0].shape[0] if stream else block
    for nid, cols in region.broadcast_inputs:
        a = _resident_val(plan, res_env, nid, block, B)
        stream.append(jnp.broadcast_to(a, (n_rows, cols)))
    rows = []
    for nid, cols in getattr(region, "bcast_rows", ()):
        # row-const resident extra: ONE [1, C] row broadcasts inside the
        # kernel (bit-identical to the old per-block materialization)
        a = _resident_val(plan, res_env, nid, block, B)
        if a.ndim >= 2:
            a = a[:1].reshape(1, a.shape[-1])
        elif a.ndim == 1:
            a = a[None, :]
        else:
            a = a.reshape(1, 1)
        rows.append(a)
    bias_ids = {s[4] for s in spec.steps if s[0] == "mm" and s[4] is not None}
    residents = []
    for nid in region.resident_inputs:
        a = res_env[nid]
        if nid in bias_ids and a.ndim == 2:
            # bias is (1, N) or a row-const (B, N): one row is the vector
            a = a[0]
        residents.append(a)
    out_info = tuple((g.nodes[o].shape[-1], g.nodes[o].dtype)
                     for o in region.outputs)
    outs = region_call(spec, stream, rows, residents, out_info,
                       bm=cfg.bm if cfg is not None else 128)
    for nid, o in zip(region.outputs, outs):
        env[nid] = o


# per-graph compile cache for the thin wrapper below: repeat calls with the
# same (graph, plan, HardwareConfig) reuse the CompiledGradient artifact.
# Keyed by object identity — the key holds the graph AND plan objects
# themselves (SegmentPlan hashes by identity), never id() ints: a cached
# entry keeps its plan alive, so a freed plan's recycled id can never alias
# a different plan's artifact.  Mutating a graph after executing it through
# this path is unsupported (go through core.pipeline.compile_from_graph).
_GRAPH_CACHE: dict[tuple, object] = {}


def streaming_executor(g: ComputeGraph, block: int | None = None, *,
                       plan: SegmentPlan | None = None,
                       use_pallas: bool | None = None,
                       dispatch_log: list | None = None,
                       config=None):
    """Returns f(*inputs) that executes the SegmentPlan as a block pipeline.

    Thin wrapper over the compile-once/run-many layer (DESIGN.md §4): the
    graph is compiled into a ``core.pipeline.CompiledGradient`` — residents
    precomputed once, one jitted block pipeline — or fetched from the
    per-graph cache, and the artifact's ``apply`` is returned.  Peak live
    memory ~ residents + one block working set, as before.

    Hardware parameters come from ``config`` (a ``HardwareConfig``); the
    ``block`` / ``use_pallas`` kwargs are conveniences folded into it.
    ``use_pallas`` selects per-segment Pallas kernel dispatch (fused_chain /
    stream_matmul / siren_layer); the default enables it on TPU and falls
    back to the per-node interpreter elsewhere (kernels themselves also run
    in interpret mode off-TPU, so ``use_pallas=True`` is valid — just slower
    — on CPU).  ``dispatch_log``, if given, receives one
    ``(id, kind, kernel)`` entry per KERNEL INVOCATION of a block step:
    when BOTH ``config.fuse_regions`` (the default) and Pallas dispatch are
    on, a fused region logs a single
    ``(region id, "FusedRegion", "region[...]")`` entry and every other
    segment its classic ``(segment id, kind, kernel)``; with ``use_pallas``
    off (the CPU auto default) the log is per-segment interpret entries —
    region megakernels only dispatch under Pallas.
    """
    from repro.core.config import as_hardware_config
    from repro.core.pipeline import compile_from_graph
    from repro.obs.metrics import counter

    cfg = as_hardware_config(config, block=block,
                             use_pallas=use_pallas).resolved()
    key = (g, plan, cfg)
    cg = _GRAPH_CACHE.get(key)
    if cg is None:
        counter("graph_cache_misses",
                "streaming_executor per-graph cache misses").inc()
        cg = compile_from_graph(g, config=cfg, plan=plan, emit_source=False)
        _GRAPH_CACHE[key] = cg
    else:
        counter("graph_cache_hits",
                "streaming_executor per-graph cache hits").inc()
    if dispatch_log is not None:
        dispatch_log.extend(cg.dispatch)
    return cg.apply


# ---------------------------------------------------------------------------
# analytic memory accounting (paper Table I "Memory" analogue)
# ---------------------------------------------------------------------------

def _nbytes(node: Node) -> int:
    return node.size * jnp.dtype(node.dtype).itemsize


def buffered_peak_bytes(g: ComputeGraph) -> int:
    """Liveness-based peak memory of the buffered schedule (an OPTIMISTIC
    baseline: real eager frameworks do not pack this tightly).  Parameters
    (Const nodes) are never freed."""
    order = g.topo_order()
    last_use: dict[int, int] = {}
    for t, nid in enumerate(order):
        for i in g.nodes[nid].inputs:
            last_use[i] = t
    for o in g.outputs:
        last_use[o] = len(order)
    live = 0
    peak = 0
    for t, nid in enumerate(order):
        live += _nbytes(g.nodes[nid])
        peak = max(peak, live)
        for i in g.nodes[nid].inputs:
            if last_use.get(i) == t and g.nodes[i].op != "Const":
                live -= _nbytes(g.nodes[i])
    return peak


def buffered_total_bytes(g: ComputeGraph) -> int:
    """Sum of every tensor in the graph — the eager-framework analogue the
    paper's CPU/GPU baselines exhibit (each kernel allocates its output;
    intermediates are not liveness-packed within the op stream)."""
    return sum(_nbytes(n) for n in g.nodes.values())


def streaming_peak_bytes(g: ComputeGraph, design, depths: dict[int, int], *,
                         plan: SegmentPlan | None = None) -> int:
    """Residents + FIFO memory (depths x block bytes) — the dataflow memory.

    Derived from the same SegmentPlan that executes and maps to FIFOs, so the
    accounting sees exactly the segments that run.  Row-constant residents
    (reverse-mode seeds and their derivatives) store ONE row — their content
    is identical across the batch, so the dataflow design re-broadcasts a
    single block."""
    if plan is None:
        plan = build_segment_plan(g)
    resident_ids, rc = plan.resident, plan.rowconst
    res = 0
    for i in resident_ids:
        n = g.nodes[i]
        b = _nbytes(n)
        if i in rc and n.shape and n.shape[0] > 1:
            b //= n.shape[0]
        res += b
    fifo = design.fifo_bytes(depths)
    return res + fifo
