"""CompiledGradient — the compile-once / run-many front door (DESIGN.md §4).

INR-Arch's compiler is an end-to-end ARTIFACT pipeline (paper Secs.
3.2.1-3.2.5): extract the nth-order gradient graph, optimize it, partition it
into stream-kernel segments, configure the hardware parameters, size the
FIFOs, and emit code ONCE — then stream many queries through the result.
This module is that front door:

    compile_gradient(fn, order, example_coords) -> CompiledGradient

Every hardware knob lives in one frozen ``HardwareConfig`` (DESIGN.md §5):
block size, serving chunk, dataflow FIFO granule, per-segment MM parallelism,
Pallas dispatch, FIFO alpha.  Pass ``config=HardwareConfig(...)`` to pin it,
``config="auto"`` to let ``core.autoconfig`` pick it with the dataflow
latency oracle (the paper's automatic hardware-parameter configuration), or
nothing for the defaults.

The artifact carries everything every downstream layer needs — the optimized
ComputeGraph, the SegmentPlan (MM segments stamped with their parallelism),
the precomputed residents (weights and const-derived tensors, the paper's
on-chip memory), the static Pallas dispatch table, the emitted codegen source
(which records the config), and the FIFO-optimized dataflow summary — plus
two execution entry points:

  * ``apply(*inputs)``        — the classic plan-batch streaming execution
                                (what ``streaming_executor`` returns);
  * ``apply_batched(coords)`` — the SERVING path: pads an arbitrary number of
                                query rows to a block multiple and streams
                                them through the one jitted block pipeline.

Repeat compilations are cache hits: an in-process cache keyed by
``(fn identity, order, coord shape/dtype, resolved HardwareConfig)`` returns
the SAME artifact object with no re-trace — the amortization PatchINR argues
for in scalable INR inference, and what a heavy-traffic serving path
requires.  Distinct configs are distinct artifacts.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import codegen
from repro.core.config import (DEFAULT_CONFIG, HardwareConfig,
                               as_hardware_config)
from repro.core.executor import (_eval_node, _run_region, _run_segment,
                                 check_streamable)
from repro.core.graph import ComputeGraph
from repro.core.segment import (SegmentPlan, apply_hardware_config,
                                build_segment_plan, dispatch_table,
                                INTERPRET, _p)
from repro.obs.metrics import MetricsView, counter as _obs_counter
from repro.obs.tracing import TRACER


def binary_parts(nb: int) -> list[int]:
    """The powers of two that sum to ``nb``, largest first (10 -> [8, 2]):
    the block counts ``apply_batched`` dispatches a request in."""
    return [1 << b for b in reversed(range(nb.bit_length())) if nb >> b & 1]


class CompiledGradient:
    """Frozen compile-once / run-many pipeline artifact.

    Treat instances as immutable: they are shared via the compile cache, so
    mutating one corrupts every holder.  All fields are set at compile time,
    with two documented exceptions that never change what the artifact
    computes: the dataflow summaries are computed lazily (the FIFO-depth
    search can take minutes on large graphs) and then cached on the
    artifact, keyed by their parameters; and ``autoconfig`` is a write-once
    metadata slot — ``None`` unless/until a ``config="auto"`` request
    resolves to this artifact's config, at which point the search record is
    attached (None -> AutoConfigResult, monotonic, set at most once).
    """

    def __init__(self, graph: ComputeGraph, plan: SegmentPlan, *,
                 config: HardwareConfig, residents: dict, dispatch: list,
                 source: str | None, fn=None, order: int | None = None,
                 autoconfig=None, region_plan=None):
        self.graph = graph
        self.plan = plan
        self.config = config              # resolved HardwareConfig
        self.residents = residents        # node id -> concrete jax.Array
        self.dispatch = dispatch          # one (id, kind, kernel) per kernel
        self.source = source              # emitted Python module (codegen)
        self.fn = fn                      # original INR fn (None via graph path)
        self.order = order
        self.autoconfig = autoconfig      # AutoConfigResult when config="auto"
        self.region_plan = region_plan    # RegionPlan (None: per-segment)
        self.provenance = "trace"         # "trace" | "store" (set on restore)
        self.cache_hits = 0               # in-process hits served (metadata)
        self.perf_model = None            # per-unit predictions (obs.drift)
        self._signature = None            # lazy architecture signature
        self._stored_in: set[str] = set()  # store roots known to hold this
        self._dataflow: dict[tuple, dict] = {}
        from repro.core.segment import segment_dispatch
        self._decisions = {
            s.id: (segment_dispatch(plan, s) if config.use_pallas
                   else INTERPRET) for s in plan.segments}
        self._streamed_outs = [o for o in graph.outputs
                               if o not in plan.resident]
        # the one jitted block pipeline (serving granule) ...
        self._block_apply = jax.jit(self._make_block_fn())
        # ... its lax.map over a leading block axis, one trace per map
        # length (apply_batched's binary parts, the async engine's chunks) ...
        self._chunk_apply = jax.jit(self._make_chunk_fn())
        # ... and the classic full-plan-batch streaming execution
        self.apply = jax.jit(self._make_apply())

    # the old scattered knobs, now views of the one config
    @property
    def block(self) -> int:
        return self.config.block

    @property
    def use_pallas(self) -> bool:
        return self.config.use_pallas

    # -- execution ---------------------------------------------------------

    def resident_block_fn(self):
        """The per-block pipeline parameterized by its resident environment:
        ``f(res_env, *xblk) -> streamed outs``.  This is what the multi-INR
        serving path vmaps over a stacked resident axis — the plan, dispatch
        decisions, and block geometry are weight-independent, so ONE such
        function serves every weight set of the architecture.

        With Pallas dispatch and a region plan, fused regions execute as ONE
        megakernel each (``_run_region``): intermediates never leave VMEM.
        Everything else runs segment-by-segment as before."""
        plan, g = self.plan, self.graph
        decisions = self._decisions
        block, B = self.config.block, plan.batch
        input_nodes = [g.nodes[i] for i in plan.inputs]
        streamed_outs = self._streamed_outs

        # execution units, fixed at compile time: fused regions dispatch as
        # megakernels only under Pallas (interpreted runs gain nothing)
        if self.region_plan is not None and self.config.use_pallas:
            units = self.region_plan.units()
        else:
            units = [("seg", s) for s in plan.segments]

        def block_fn(res_env, *xblk):
            env = {n.id: xblk[_p(n, "idx")] for n in input_nodes}
            for kind, u in units:
                if kind == "region":
                    _run_region(plan, u, env, res_env, block, B)
                else:
                    env[u.output] = _run_segment(plan, u, decisions[u.id],
                                                 env, res_env, block, B)
            return tuple(env[o] for o in streamed_outs)
        return block_fn

    def _make_block_fn(self):
        res_fn = self.resident_block_fn()
        res_env = self.residents

        def block_fn(*xblk):
            return res_fn(res_env, *xblk)
        return block_fn

    def _make_chunk_fn(self):
        block_fn = self._make_block_fn()

        def chunk_fn(xchunk):              # [n_blocks, block, ...features]
            return jax.lax.map(lambda b: block_fn(b), xchunk)
        return chunk_fn

    def _make_apply(self):
        plan, g = self.plan, self.graph
        res_env, block = self.residents, self.config.block
        B = plan.batch
        n_blocks = B // block
        block_fn = self._make_block_fn()
        streamed_outs = self._streamed_outs

        def apply(*inputs):
            if streamed_outs:
                xb = tuple(x.reshape(n_blocks, block, *x.shape[1:])
                           for x in inputs)
                outs = jax.lax.map(lambda b: block_fn(*b), xb)
                vals = iter(o.reshape(B, *o.shape[2:]) for o in outs)
            else:
                vals = iter(())
            return tuple(res_env[o] if o in plan.resident else next(vals)
                         for o in g.outputs)
        return apply

    def apply_chunk(self, xchunk):
        """One jitted CHUNK step of the serving path: ``xchunk`` is
        [n_blocks, block, ...features] already split into blocks; returns the
        streamed outputs, each [n_blocks, block, ...].  This is the granule
        the async serving engine's continuous-batching loop dispatches, so
        ADMISSION can happen between chunks (DESIGN.md §8).  Shape-stable
        callers (full ``config.chunk_blocks`` chunks) hit one compiled
        trace."""
        return self._chunk_apply(xchunk)

    def apply_block(self, xblk):
        """One jitted BLOCK step ([block, ...features] -> streamed outs) —
        the async engine's remainder granule."""
        return self._block_apply(xblk)

    def streamed_outputs(self) -> list[int]:
        """Graph outputs served by the streaming path, in output order (the
        rest are residents, read from ``resident_output``)."""
        return list(self._streamed_outs)

    def resident_output(self, o: int, n: int):
        """A resident (const-derived) output broadcast to ``n`` rows."""
        return self._resident_output(o, n)

    def apply_batched(self, coords):
        """Serve an arbitrary number of query rows through the compiled
        pipeline.

        ``coords`` is [N, ...features] for any N: the batch is padded to a
        block multiple (edge rows replicated — padding never reaches the
        caller) and reshaped once to its ``nb`` blocks; ``nb`` streams as
        its binary parts, largest first, each ONE call of the jitted block
        ``lax.map`` (75 rows at block 8: 10 blocks = 8 + 2, two calls).
        So a request makes at most ``popcount(nb)`` dispatches, an artifact
        compiles at most ``floor(log2(nb_max)) + 1`` map lengths however
        many batch sizes it serves, and no row is computed beyond the block
        pad.  The first N rows of each output are returned.
        """
        if len(self.plan.inputs) != 1:
            raise ValueError("apply_batched serves single-input (coordinate) "
                             "pipelines; use apply() for multi-input graphs")
        coords = jnp.asarray(coords)
        n = coords.shape[0]
        block = self.config.block
        if n == 0:
            return tuple(
                self._resident_output(o, 0) if o in self.plan.resident
                else jnp.zeros((0,) + tuple(self.graph.nodes[o].shape[1:]),
                               self.graph.nodes[o].dtype)
                for o in self.graph.outputs)
        # spans on the profiler's clock: each chunk span only enqueues
        # device work, so device idle inside one is host dispatch
        with TRACER.span("pipeline.pad", cat="pipeline"):
            pad = (-n) % block
            if pad:
                edge = jnp.broadcast_to(coords[-1:],
                                        (pad,) + coords.shape[1:])
                coords = jnp.concatenate([coords, edge])
            nb = coords.shape[0] // block
            xb = coords.reshape(nb, block, *coords.shape[1:])

        pieces: list[tuple] = []
        start = 0
        for size in binary_parts(nb):
            with TRACER.span("pipeline.chunk", cat="pipeline", blocks=size):
                part = xb if size == nb else xb[start:start + size]
                pieces.append(self._chunk_apply(part))  # each [size, block, ...]
                _DISPATCHES.inc()
            start += size

        with TRACER.span("pipeline.stitch", cat="pipeline"):
            def rows(col):
                o = jnp.concatenate(col) if len(col) > 1 else col[0]
                o = o.reshape(nb * block, *o.shape[2:])
                return o[:n] if pad else o
            streamed = iter(rows(col) for col in zip(*pieces))
            return tuple(self._resident_output(o, n)
                         if o in self.plan.resident
                         else next(streamed) for o in self.graph.outputs)

    def _resident_output(self, o: int, n: int):
        v = self.residents[o]
        if (o in self.plan.rowconst and v.ndim
                and v.shape[:1] == (self.plan.batch,)):
            # row-constant resident output: one row serves any batch size
            v = jnp.broadcast_to(v[:1], (n,) + v.shape[1:])
        return v

    # -- the rest of the artifact ------------------------------------------

    def dataflow_summary(self, *, dataflow_block: int | None = None,
                         mm_parallel: int | None = None) -> dict:
        """FIFO-optimized dataflow summary for this plan (lazy; the FIFO
        search is the expensive part of the paper's compiler).

        Defaults come from the artifact's HardwareConfig — ``dataflow_block``
        from ``config.dataflow_block``, MM parallelism per segment from the
        config's stamps.  Passing ``mm_parallel`` explicitly applies one
        uniform factor instead (what the table sweeps do).  Summaries are
        cached on the artifact KEYED BY THOSE PARAMETERS, so different
        arguments get different (correct) summaries rather than the first
        call's."""
        cfg = self.config
        db = dataflow_block if dataflow_block is not None else cfg.dataflow_block
        key = (db, mm_parallel if mm_parallel is not None
               else ("config", cfg.mm_parallel, cfg.mm_parallel_per_segment))
        cached = self._dataflow.get(key)
        if cached is None:
            from repro.core.dataflow import map_to_dataflow
            from repro.core.fifo_opt import optimize_fifo_depths
            with TRACER.span("compile.dataflow_map", cat="compile",
                             dataflow_block=db):
                design = map_to_dataflow(
                    self.graph, block=db, mm_parallel=mm_parallel,
                    plan=self.plan,
                    config=None if mm_parallel is not None else cfg,
                    region_plan=None if mm_parallel is not None
                    else self.region_plan)
            with TRACER.span("compile.fifo_opt", cat="compile",
                             streams=len(design.streams)):
                res = optimize_fifo_depths(design, config=cfg)
            cached = {"design": design, "fifo": res, **res.summary()}
            self._dataflow[key] = cached
        return cached

    @property
    def signature(self) -> str:
        """Weight-independent architecture signature (graph structure +
        order + resolved config) — the artifact store's canonical key.
        Computed lazily and cached; store-restored artifacts carry the
        signature they were stored under."""
        if self._signature is None:
            from repro.serve.store import arch_signature
            self._signature = arch_signature(self.graph, self.order,
                                             self.config)
        return self._signature

    def describe(self) -> str:
        kernels = [k for _, _, k in self.dispatch if k != INTERPRET]
        prov = self.provenance
        if self.cache_hits:
            prov += f" (+{self.cache_hits} in-process hits)"
        lines = [f"CompiledGradient(order={self.order}, "
                 f"config=[{self.config.describe()}]): "
                 f"{len(self.graph.nodes)} nodes, "
                 f"{len(self.plan.segments)} segments, "
                 f"{len(self.residents)} residents, "
                 f"{len(kernels)} Pallas-dispatched kernels",
                 f"  provenance: {prov}",
                 f"  signature: {self.signature}"]
        if self.autoconfig is not None:
            lines.append(f"  {self.autoconfig.describe()}")
        lines.append(self.plan.describe())
        if self.region_plan is not None:
            lines.append(self.region_plan.describe())
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# compilation
# ---------------------------------------------------------------------------

def compile_from_graph(g: ComputeGraph, *,
                       config: HardwareConfig | None = None,
                       block: int | None = None,
                       use_pallas: bool | None = None,
                       plan: SegmentPlan | None = None,
                       emit_source: bool = True,
                       fn=None, order: int | None = None,
                       autoconfig=None) -> CompiledGradient:
    """Compile an already-extracted, optimized ComputeGraph into a
    CompiledGradient.  The plan is built once (or taken as given) and drives
    the executor, the emitted source, and the lazy dataflow summary alike —
    nothing downstream re-derives it.

    Hardware parameters come from ``config``; ``block`` / ``use_pallas`` are
    conveniences folded into it (``as_hardware_config``)."""
    assert check_streamable(g), "graph is not batch-streamable"
    cfg = as_hardware_config(config, block=block,
                             use_pallas=use_pallas).resolved()
    if plan is None:
        with TRACER.span("compile.segment_plan", cat="compile") as sp:
            plan = build_segment_plan(g, config=cfg)
            sp.set(segments=len(plan.segments))
    B = plan.batch
    cfg = cfg.clamped(B)
    if B % cfg.block != 0:
        raise ValueError(f"plan batch {B} is not a multiple of block "
                         f"{cfg.block}")
    if plan.config != cfg:
        # a caller-provided plan (or a pre-clamp build) gets the final
        # config stamped so MM segments carry their parallelism; a plan
        # already stamped with a DIFFERENT config is copied, not mutated —
        # earlier artifacts sharing it keep the config they compiled with
        plan = apply_hardware_config(plan, cfg)

    # the region schedule (DESIGN.md §7): deterministic for (plan, config),
    # so executor, codegen, and dataflow all see the same fusion
    region_plan = None
    if cfg.fuse_regions:
        from repro.core.regions import build_region_plan
        with TRACER.span("compile.region_plan", cat="compile") as sp:
            region_plan = build_region_plan(plan, cfg)
            sp.set(regions=len(region_plan.regions))

    if not cfg.use_pallas:
        dispatch = [(s.id, s.kind, INTERPRET) for s in plan.segments]
    elif region_plan is not None:
        from repro.core.regions import region_dispatch_table
        dispatch = region_dispatch_table(plan, region_plan)
    else:
        dispatch = dispatch_table(plan)
    # a dispatch entry of a segment that no kernel runs covers its nodes
    by_id = {s.id: s for s in plan.segments}
    _INTERP_NODES.inc(sum(len(by_id[i].nodes) for i, kind, kernel in dispatch
                          if kernel == INTERPRET), path="serve")
    for reason, n in (region_plan.cuts if region_plan is not None
                      else {}).items():
        _REGION_CUTS.inc(n, reason=reason)

    # precompute residents once: the paper's on-chip tensors, never re-derived
    residents: dict[int, jax.Array] = {}
    with TRACER.span("compile.residents", cat="compile"):
        for nid in plan.resident_order():
            n = g.nodes[nid]
            if n.op == "Const":
                residents[nid] = jnp.asarray(n.const)
            else:
                residents[nid] = _eval_node(n, [residents[i]
                                                for i in n.inputs])

    if emit_source:
        with TRACER.span("compile.codegen", cat="compile"):
            source = codegen.emit_python(g, plan=plan, config=cfg,
                                         region_plan=region_plan)
    else:
        source = None
    cg = CompiledGradient(g, plan, config=cfg, residents=residents,
                          dispatch=dispatch, source=source, fn=fn,
                          order=order, autoconfig=autoconfig,
                          region_plan=region_plan)
    # the oracle's per-unit predictions, recorded on the artifact so a
    # DriftReport can later compare them against measured wall (obs.drift)
    from repro.obs.drift import build_perf_model
    cg.perf_model = build_perf_model(plan, region_plan, cfg)
    return cg


# ---------------------------------------------------------------------------
# the compile cache (compile once, serve many)
# ---------------------------------------------------------------------------

_CACHE: dict[tuple, CompiledGradient] = {}
# the compile-layer accounting, now registry metrics (DESIGN.md §10); the
# dict-shaped view keeps every ``_STATS["hits"] += 1`` call site and every
# external reader working verbatim
_STATS = MetricsView({
    "hits": _obs_counter("compile_cache_hits",
                         "in-process compile cache hits"),
    "misses": _obs_counter("compile_cache_misses",
                           "in-process compile cache misses"),
    "store_hits": _obs_counter("compile_store_hits",
                               "artifact-store restore hits"),
    "store_misses": _obs_counter("compile_store_misses",
                                 "artifact-store restore misses"),
    "store_puts": _obs_counter("compile_store_puts",
                               "artifacts persisted to a store"),
})
_DISPATCHES = _obs_counter("pipeline_dispatches",
                           "jitted block-pipeline dispatches made by "
                           "apply_batched")
# what the compiler left outside the kernels, counted per compiled graph
_INTERP_NODES = _obs_counter(
    "compile_interp_nodes",
    "graph nodes a compiled artifact runs on the per-node interpreter, "
    "outside every Pallas kernel (path=serve: the block pipeline; "
    "path=fit: the streamed fit's walk)")
_REGION_CUTS = _obs_counter(
    "compile_region_cuts",
    "region boundaries the scheduler made, by reason: vmem (joining would "
    "pass the budget), op (a segment with no region step, or operands one "
    "kernel cannot share), forced (a config cut point)")


def _fn_key(fn):
    """fn identity: the object itself when hashable (functions hash by
    identity), else id() — the cached artifact keeps fn alive either way."""
    try:
        hash(fn)
        return fn
    except TypeError:
        return id(fn)


def compile_cache_info() -> dict:
    """One view of EVERY compile-layer cache: the compile_gradient artifact
    cache, the per-graph cache behind ``executor.streaming_executor``, the
    per-artifact keyed ``dataflow_summary`` caches, the monotonic tracer
    counter, and the artifact-store hit/miss/put accounting."""
    from repro.core import executor, trace
    artifacts = {id(cg): cg for cg in _CACHE.values()}
    artifacts.update((id(cg), cg) for cg in executor._GRAPH_CACHE.values())
    return {"hits": _STATS["hits"], "misses": _STATS["misses"],
            "size": len(_CACHE),
            "graph_cache_size": len(executor._GRAPH_CACHE),
            "dataflow_summaries": sum(len(cg._dataflow)
                                      for cg in artifacts.values()),
            "traces": trace.TRACE_CALLS,
            "store_hits": _STATS["store_hits"],
            "store_misses": _STATS["store_misses"],
            "store_puts": _STATS["store_puts"]}


def clear_compile_cache() -> None:
    """Drop every cached artifact: the compile_gradient cache, the per-graph
    cache behind executor.streaming_executor, and (with them) every cached
    per-artifact dataflow summary.  Store hit/miss accounting resets too;
    the tracer counter is monotonic by design (tests measure deltas)."""
    from repro.core import executor
    _CACHE.clear()
    _BANK_CACHE.clear()
    _FIT_CACHE.clear()
    for k in _STATS:
        _STATS[k] = 0
    executor._GRAPH_CACHE.clear()


def _trace_graph(fn, order: int, trace_b: int, shape, dtype) -> ComputeGraph:
    """Extract + optimize the order-th gradient graph of fn at the trace
    batch (the front half of the compiler, shared by every config)."""
    # gradnet lives one layer up; import lazily to keep core's import DAG flat
    from repro.core.passes import optimize
    from repro.core.trace import extract_graph
    from repro.inr.gradnet import paper_gradients

    abstract = jax.ShapeDtypeStruct((trace_b,) + tuple(shape[1:]), dtype)
    out = jax.eval_shape(fn, abstract)
    gfn = paper_gradients(fn, order, out_features=out.shape[-1],
                          in_features=shape[-1])
    with TRACER.span("compile.trace", cat="compile", order=order,
                     trace_b=trace_b):
        g = extract_graph(gfn, abstract)
    with TRACER.span("compile.passes", cat="compile") as sp:
        optimize(g)
        sp.set(nodes=len(g.nodes))
    return g


def compile_gradient(fn, order: int, example_coords, *,
                     config: HardwareConfig | str | None = None,
                     block: int | None = None,
                     use_pallas: bool | None = None,
                     store=None,
                     base_config: HardwareConfig | None = None,
                     ) -> CompiledGradient:
    """The pipeline front door: compile-or-hit the full INR-Arch compiler for
    the ``order``-th gradient computation of INR ``fn``.

    ``example_coords`` only contributes shape and dtype (a concrete array or
    a ``jax.ShapeDtypeStruct`` both work); its batch dim is rounded up to a
    block multiple for the trace (``apply`` expects that rounded batch;
    ``apply_batched`` serves any N regardless).

    ``config`` selects the hardware parameters:

      * a ``HardwareConfig`` — used as given (``block`` / ``use_pallas``
        kwargs override its fields);
      * ``None`` — ``DEFAULT_CONFIG`` (with the same overrides);
      * ``"auto"`` — ``core.autoconfig.resolve_config`` picks block and
        per-MM-segment parallelism with the dataflow latency oracle,
        rejecting deadlock-flagged candidates (the paper's automatic
        hardware-parameter configuration); the result rides on the artifact
        as ``cg.autoconfig``.  ``base_config`` (auto mode only) seeds the
        search: pass e.g. ``DEFAULT_CONFIG.replace(n_shards=4)`` so the
        oracle models the cross-shard input stream of a sharded serving
        mesh (DESIGN.md §8) — every candidate inherits its non-searched
        fields.

    Repeat calls with the same (fn identity, order, coord shape/dtype,
    resolved HardwareConfig) return the SAME artifact — no re-trace, no
    re-optimize, no re-plan.  The cache is keyed on the RESOLVED config, so
    distinct configs get distinct entries, and ``config="auto"`` shares its
    entry with an explicit request for whatever config it resolved to.

    ``store`` (an ``serve.ArtifactStore`` or a directory path) adds the
    DISK level, making this a three-level lookup: in-process cache -> store
    -> trace+compile+persist.  A store hit rebuilds the artifact from the
    persisted graph/config/weights without a single tracer invocation; a
    miss compiles as usual and persists the result, so the NEXT replica
    cold-starts warm.
    """
    shape = tuple(example_coords.shape)
    dtype = str(jnp.dtype(example_coords.dtype))
    if store is not None:
        from repro.serve.store import as_store
        store = as_store(store)

    if isinstance(config, str):
        if config != "auto":
            raise ValueError(f"config must be a HardwareConfig, None, or "
                             f"'auto'; got {config!r}")
        return _compile_auto(fn, order, shape, dtype, block=block,
                             use_pallas=use_pallas, store=store,
                             base_config=base_config)
    if base_config is not None:
        raise ValueError("base_config only seeds config='auto'; pass it as "
                         "config= for an explicit request")

    cfg = as_hardware_config(config, block=block,
                             use_pallas=use_pallas).resolved()
    # key on the block-rounded TRACE batch, so every shape that compiles to
    # the same artifact shares one cache entry
    trace_b = shape[0] + (-shape[0]) % cfg.block
    key = (_fn_key(fn), int(order), (trace_b,) + shape[1:], dtype,
           cfg.clamped(trace_b))
    hit = _CACHE.get(key)
    if hit is not None:
        _STATS["hits"] += 1
        hit.cache_hits += 1
        if store is not None and store.root not in hit._stored_in:
            # a store handed in late still ends up populated — but a root
            # this artifact is known to live in costs the hit path nothing
            store.ensure(hit, request_key=_request_key(fn, order, trace_b,
                                                       shape, dtype, cfg))
            hit._stored_in.add(store.root)
        return hit
    _STATS["misses"] += 1

    rk = None
    if store is not None:
        rk = _request_key(fn, order, trace_b, shape, dtype, cfg)
        cg = store.restore_request(rk)
        if cg is not None:
            _STATS["store_hits"] += 1
            if cg.fn is None:
                cg.fn = fn
            _CACHE[key] = cg
            return cg
        _STATS["store_misses"] += 1

    with TRACER.span("compile", cat="compile", order=order,
                     mode="explicit"):
        g = _trace_graph(fn, order, trace_b, shape, dtype)
        cg = compile_from_graph(g, config=cfg, fn=fn, order=order)
    _CACHE[key] = cg
    if store is not None:
        store.put(cg, request_key=rk)
        cg._stored_in.add(store.root)
        _STATS["store_puts"] += 1
    return cg


def _request_key(fn, order, trace_b, shape, dtype, cfg):
    """Disk-index key for one request (None when fn has no stable
    cross-process fingerprint — the disk level is then skipped)."""
    from repro.serve.store import request_key
    return request_key(fn, order, (trace_b,) + tuple(shape[1:]), dtype,
                       cfg.clamped(trace_b))


def _compile_auto(fn, order: int, shape, dtype, *,
                  block: int | None = None,
                  use_pallas: bool | None = None,
                  store=None,
                  base_config: HardwareConfig | None = None,
                  ) -> CompiledGradient:
    """config="auto": trace once, let autoconfig pick the HardwareConfig,
    compile with the winner, and cache under BOTH the auto request and the
    resolved config (so explicit requests for the winner hit the same
    artifact).  With a store, the auto request gets its own disk-index
    binding — a replica restoring it skips the trace AND the search, and
    the artifact carries the persisted AutoConfigResult."""
    from repro.core.autoconfig import resolve_config

    base = as_hardware_config(base_config, block=block,
                              use_pallas=use_pallas).resolved()
    # round the trace batch to the LCM-ish of the block candidates (multiples
    # of 8) so the search may pick any block that divides it
    trace_b = shape[0] + (-shape[0]) % 8
    auto_key = (_fn_key(fn), int(order), (trace_b,) + tuple(shape[1:]), dtype,
                "auto", base)
    hit = _CACHE.get(auto_key)
    if hit is not None:
        _STATS["hits"] += 1
        hit.cache_hits += 1
        return hit
    _STATS["misses"] += 1

    rk = None
    if store is not None:
        from repro.serve.store import request_key
        rk = request_key(fn, order, (trace_b,) + tuple(shape[1:]), dtype,
                         base, mode="auto")
        cg = store.restore_request(rk)
        if cg is not None:
            _STATS["store_hits"] += 1
            if cg.fn is None:
                cg.fn = fn
            _CACHE[auto_key] = cg
            _CACHE[(_fn_key(fn), int(order), (trace_b,) + tuple(shape[1:]),
                    dtype, cg.config)] = cg
            return cg
        _STATS["store_misses"] += 1

    with TRACER.span("compile", cat="compile", order=order, mode="auto"):
        g = _trace_graph(fn, order, trace_b, shape, dtype)
        with TRACER.span("compile.segment_plan", cat="compile"):
            plan = build_segment_plan(g)
        # on TPU the analytic winner is refined against REAL apply_batched
        # timings (block + bm/bn tile re-rank); off-TPU the search stays
        # analytic — deterministic and cheap, what the tests rely on
        measure = None
        if jax.default_backend() == "tpu":
            from repro.core.autoconfig import make_apply_batched_measure
            measure = make_apply_batched_measure(g, plan)
        result = resolve_config(g, plan, base=base, measure=measure)
        cfg = result.config

        resolved_key = (_fn_key(fn), int(order),
                        (trace_b,) + tuple(shape[1:]),
                        dtype, cfg.clamped(trace_b))
        cg = _CACHE.get(resolved_key)
        if cg is None:
            cg = compile_from_graph(g, config=cfg, plan=plan, fn=fn,
                                    order=order, autoconfig=result)
            _CACHE[resolved_key] = cg
        elif cg.autoconfig is None:
            # the search resolved to a config already compiled explicitly
            # (e.g. the default); share the artifact and attach the record
            cg.autoconfig = result
    _CACHE[auto_key] = cg
    if store is not None:
        store.put(cg, request_key=rk)
        cg._stored_in.add(store.root)
        _STATS["store_puts"] += 1
    return cg


# ---------------------------------------------------------------------------
# the filter-bank compiler (DESIGN.md §9): F filters, one megakernel pipeline
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BankReport:
    """Compile-time accounting of the bank vs the per-filter loop it
    replaces — every field is a deterministic compiler output (no timing).

    The "loop" numbers are the SUM over per-filter artifacts at the same
    HardwareConfig: F separate compiles, each re-deriving the shared
    gradient-feature prefix.  The bank merges the filter graphs, hash-conses
    the prefix to one computation, and serves every filter output from one
    multi-sink region pipeline — so each bank column is never worse, and the
    prefix sharing makes dispatches/HBM strictly better for F >= 2."""
    n_heads: int
    nodes_bank: int
    nodes_loop: int
    dispatches_bank: int
    dispatches_loop: int
    hbm_block_bank: int
    hbm_block_loop: int
    row_cycles_bank: int
    row_cycles_loop: int

    def describe(self) -> str:
        def x(a, b):
            return f"{b / max(a, 1):.1f}x"
        return (f"BankReport({self.n_heads} heads): "
                f"nodes {self.nodes_bank} vs loop {self.nodes_loop} "
                f"({x(self.nodes_bank, self.nodes_loop)}), "
                f"dispatches {self.dispatches_bank} vs "
                f"{self.dispatches_loop} "
                f"({x(self.dispatches_bank, self.dispatches_loop)}), "
                f"hbm/block {self.hbm_block_bank} vs {self.hbm_block_loop} "
                f"({x(self.hbm_block_bank, self.hbm_block_loop)}), "
                f"row-cycles {self.row_cycles_bank} vs "
                f"{self.row_cycles_loop}")


class CompiledBank:
    """F filter pipelines compiled as ONE multi-output artifact.

    Wraps the ``CompiledGradient`` of the MERGED graph (every standard
    artifact capability — serving paths, store persistence, dataflow
    summaries — comes from it unchanged) plus the bank bookkeeping: head
    count/order and the compile-time ``BankReport`` (None when restored
    from a store, where the per-filter graphs were never re-traced).
    Output ``j`` of every serving call is filter ``j``'s output, in the
    order the heads were given."""

    def __init__(self, cg: CompiledGradient, *, n_heads: int, order: int,
                 report: BankReport | None = None, fn=None, heads=None):
        self.cg = cg
        self.n_heads = n_heads
        self.order = order
        self.report = report
        self.fn = fn
        self.heads = tuple(heads) if heads is not None else None

    @property
    def graph(self) -> ComputeGraph:
        return self.cg.graph

    @property
    def plan(self) -> SegmentPlan:
        return self.cg.plan

    @property
    def config(self) -> HardwareConfig:
        return self.cg.config

    @property
    def region_plan(self):
        return self.cg.region_plan

    @property
    def dispatch(self):
        return self.cg.dispatch

    @property
    def signature(self) -> str:
        return self.cg.signature

    def apply(self, coords):
        return self.cg.apply(coords)

    def apply_batched(self, coords):
        """Serve any N rows; returns a tuple of F arrays, one per filter."""
        return self.cg.apply_batched(coords)

    def describe(self) -> str:
        lines = [f"CompiledBank({self.n_heads} heads, order={self.order})"]
        if self.report is not None:
            lines.append("  " + self.report.describe())
        lines.append(self.cg.describe())
        return "\n".join(lines)


_BANK_CACHE: dict[tuple, CompiledBank] = {}

# compile_fit artifacts, keyed (CompiledGradient identity, Objective,
# checkpoint cuts) — the heavy compile half already dedupes through _CACHE /
# the store, so fit keys ride on the cg object itself (which the entry
# keeps alive).  Populated by repro.fit.compile; cleared with its siblings.
_FIT_CACHE: dict[tuple, object] = {}


def compile_fit(fn, loss, order: int, example_coords, *, params,
                config=None, block=None, use_pallas=None, store=None,
                checkpoints="auto"):
    """Streamed-fitting front door: ``compile_gradient`` for the heavy half
    (same three-level cache/store lookup), plus the online loss-gradient
    program of DESIGN.md §11.  See ``repro.fit.compile.compile_fit``."""
    from repro.fit.compile import compile_fit as _compile_fit
    return _compile_fit(fn, loss, order, example_coords, params=params,
                        config=config, block=block, use_pallas=use_pallas,
                        store=store, checkpoints=checkpoints)


def _trace_filter_graph(fn, head, order: int, trace_b: int, shape,
                        dtype) -> ComputeGraph:
    """Extract + optimize the graph of ONE filter: ``head`` applied to the
    order-th gradient feature matrix of ``fn`` (the INSP computation,
    DESIGN.md §9).  Column layout matches ``gradnet.feature_vector``."""
    from repro.core.passes import optimize
    from repro.core.trace import extract_graph
    from repro.inr.gradnet import paper_gradients

    abstract = jax.ShapeDtypeStruct((trace_b,) + tuple(shape[1:]), dtype)
    out = jax.eval_shape(fn, abstract)
    gfn = paper_gradients(fn, order, out_features=out.shape[-1],
                          in_features=shape[-1])

    def filter_fn(x):
        outs = gfn(x)
        feats = jnp.concatenate([o.reshape(o.shape[0], -1) for o in outs],
                                -1)
        return head(feats)

    g = extract_graph(filter_fn, abstract)
    optimize(g)
    return g


def _bank_report(per_head, merged: ComputeGraph,
                 cg: CompiledGradient) -> BankReport:
    """Deterministic bank-vs-loop accounting at the bank's resolved config.
    The loop columns sum per-filter plans compiled at the SAME config, so
    the comparison isolates graph sharing from hardware-parameter choice."""
    from repro.core.autoconfig import predicted_latency
    from repro.core.regions import (build_region_plan, region_dispatch_table,
                                    region_hbm_bytes_per_block)
    cfg = cg.config
    d_loop = h_loop = c_loop = n_loop = 0
    for g in per_head:
        plan = build_segment_plan(g, config=cfg)
        rp = build_region_plan(plan, cfg)
        d_loop += len(region_dispatch_table(plan, rp))
        h_loop += region_hbm_bytes_per_block(plan, rp, cfg.block)
        c_loop += predicted_latency(g, cfg, plan=plan)
        n_loop += len(g.nodes)
    rp_bank = cg.region_plan
    if rp_bank is None:
        rp_bank = build_region_plan(cg.plan, cfg)
    return BankReport(
        n_heads=len(per_head),
        nodes_bank=len(merged.nodes), nodes_loop=n_loop,
        dispatches_bank=len(region_dispatch_table(cg.plan, rp_bank)),
        dispatches_loop=d_loop,
        hbm_block_bank=region_hbm_bytes_per_block(cg.plan, rp_bank,
                                                  cfg.block),
        hbm_block_loop=h_loop,
        row_cycles_bank=predicted_latency(merged, cfg, plan=cg.plan),
        row_cycles_loop=c_loop)


def compile_bank(fn, heads, order: int, example_coords, *,
                 config: HardwareConfig | str | None = None,
                 block: int | None = None,
                 use_pallas: bool | None = None,
                 store=None,
                 base_config: HardwareConfig | None = None) -> CompiledBank:
    """Compile a FILTER BANK: every ``head`` applied to the same order-th
    gradient features of INR ``fn``, served from ONE merged pipeline.

    Each filter's graph is traced independently (head over the
    ``gradnet.feature_vector`` feature matrix), grafted into one
    multi-output graph (``graph.merge_graphs``), and hash-consed
    (``passes.dedupe_common_subtrees``) so the shared gradient-feature
    prefix — ~90% of every filter's FLOPs — collapses to a single
    computation feeding every head.  The merged graph compiles through the
    standard ``compile_from_graph`` stack: the region scheduler fuses the
    prefix and the head branches into multi-sink megakernels, so one
    streamed pass emits all F filter outputs per row tile.

    ``config`` follows ``compile_gradient``: a ``HardwareConfig``, ``None``
    (defaults), or ``"auto"`` (the dataflow oracle searches over the MERGED
    graph; ``base_config`` seeds it).  Each head must trace to exactly one
    output array.  Repeat calls with the same (fn, heads, order, coords,
    config) identities hit the in-process bank cache; ``store`` adds the
    disk level under the merged graph's architecture signature, with the
    request bound via ``serve.store.bank_request_key``.

    Returns a ``CompiledBank``; ``apply_batched(coords)`` yields a tuple of
    F arrays in head order, bit-identical to serving each filter through
    its own single-head artifact."""
    heads = tuple(heads)
    if not heads:
        raise ValueError("compile_bank needs at least one head")
    shape = tuple(example_coords.shape)
    dtype = str(jnp.dtype(example_coords.dtype))
    if store is not None:
        from repro.serve.store import as_store
        store = as_store(store)

    auto = isinstance(config, str)
    if auto and config != "auto":
        raise ValueError(f"config must be a HardwareConfig, None, or "
                         f"'auto'; got {config!r}")
    head_keys = tuple(_fn_key(h) for h in heads)
    if auto:
        base = as_hardware_config(base_config, block=block,
                                  use_pallas=use_pallas).resolved()
        trace_b = shape[0] + (-shape[0]) % 8
        key = (_fn_key(fn), head_keys, int(order),
               (trace_b,) + shape[1:], dtype, "auto", base)
        key_cfg = base
    else:
        if base_config is not None:
            raise ValueError("base_config only seeds config='auto'; pass it "
                             "as config= for an explicit request")
        cfg = as_hardware_config(config, block=block,
                                 use_pallas=use_pallas).resolved()
        trace_b = shape[0] + (-shape[0]) % cfg.block
        key = (_fn_key(fn), head_keys, int(order),
               (trace_b,) + shape[1:], dtype, cfg.clamped(trace_b))
        key_cfg = cfg.clamped(trace_b)
    hit = _BANK_CACHE.get(key)
    if hit is not None:
        _STATS["hits"] += 1
        hit.cg.cache_hits += 1
        return hit
    _STATS["misses"] += 1

    rk = None
    if store is not None:
        from repro.serve.store import bank_request_key
        rk = bank_request_key(fn, heads, order,
                              (trace_b,) + tuple(shape[1:]), dtype, key_cfg,
                              mode="auto" if auto else "explicit")
        if rk is not None:
            cg = store.restore_request(rk)
            if cg is not None:
                _STATS["store_hits"] += 1
                bank = CompiledBank(cg, n_heads=len(heads), order=order,
                                    fn=fn, heads=heads)
                _BANK_CACHE[key] = bank
                return bank
            _STATS["store_misses"] += 1

    with TRACER.span("compile.bank", cat="compile", order=order,
                     heads=len(heads)):
        per_head = [_trace_filter_graph(fn, h, order, trace_b, shape, dtype)
                    for h in heads]
        for j, gh in enumerate(per_head):
            if len(gh.outputs) != 1:
                raise ValueError(
                    f"bank head {j} traced to {len(gh.outputs)} outputs; "
                    f"each filter head must return exactly one array")
        from repro.core.graph import merge_graphs
        from repro.core.passes import optimize
        with TRACER.span("compile.passes", cat="compile"):
            merged, _ = merge_graphs(per_head)
            optimize(merged)    # dedupe_common_subtrees collapses the prefix

        autoconfig = None
        if auto:
            from repro.core.autoconfig import resolve_config
            plan = build_segment_plan(merged)
            autoconfig = resolve_config(merged, plan, base=base)
            cfg = autoconfig.config
            cg = compile_from_graph(merged, config=cfg, plan=plan,
                                    order=order, autoconfig=autoconfig)
        else:
            cg = compile_from_graph(merged, config=cfg, order=order)

    bank = CompiledBank(cg, n_heads=len(heads), order=order,
                        report=_bank_report(per_head, merged, cg),
                        fn=fn, heads=heads)
    _BANK_CACHE[key] = bank
    if store is not None:
        store.put(cg, request_key=rk)
        cg._stored_in.add(store.root)
        _STATS["store_puts"] += 1
    return bank
