"""compile_fit — the fitting half of the pipeline front door (DESIGN.md §11).

Serving streams an INR's order-n gradient outputs block-by-block through the
SegmentPlan / FusedRegion schedule; fitting needs ∂/∂θ of a LOSS over those
same outputs.  The whole-grid alternative (`jax.grad` over the full
coordinate tensor) buffers every layer activation for every row — peak
memory O(grid).  `CompiledFit` reuses the serving artifact's block pipeline
and accumulates the loss gradient ONLINE:

    for each block:  g += ∂/∂θ [ sum of masked row losses over the block ]

so reverse-mode only ever buffers ONE block's activations — peak memory
O(block x depth) — while the summed partials match the whole-grid gradient
up to float reassociation (tests gate allclose ≤ 1e-5).

Three layers cooperate:

  * the per-block forward is the SAME execution-unit walk the serving
    executor uses.  Segments run through the per-node interpreter
    (differentiable jnp); fused regions under Pallas dispatch run through
    ``kernels.region.region_grad_fn`` — forward bit-identical to serving,
    backward ONE accumulating megakernel whose per-parameter partials stay
    VMEM-resident across row tiles (one HBM flush per parameter).
  * per-unit GRADIENT CHECKPOINT CUTS (``regions.plan_fit_checkpoints``):
    units whose buffered activations would blow the VMEM budget recompute
    their interior on the backward sweep (``jax.checkpoint``) instead —
    chosen by the same liveness/byte model the region packer uses, and
    bit-invariant (identical ops replayed in identical order).
  * the resident environment (weights + derived tensors) is REBUILT
    differentiably from the trainable leaves inside every block's gradient,
    exactly as ``MultiINRArtifact`` rebuilds it per payload — so ∂loss/∂θ
    flows through weight transposes and products without any bespoke
    adjoint code.

Trainable parameters are identified the ``bind_weights`` way: each Const
node equal to a template-params leaf maps to that leaf; unmatched Consts
(w0 scalars, cotangent seeds) stay fixed.  The gradient therefore arrives
in the caller's own params pytree, and ``payload()`` round-trips fitted
leaves straight into ``ArtifactStore.put_weights`` for serving.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import _eval_node, _resident_val, _run_segment
from repro.core.regions import (fit_backward_bytes, plan_fit_checkpoints,
                                unit_act_row_bytes)
from repro.core.segment import INTERPRET
from repro.fit.objectives import Objective


# ---------------------------------------------------------------------------
# trainable-const identification
# ---------------------------------------------------------------------------

def match_trainable(cg, params):
    """Map Const nodes to template-params leaves (the ``bind_weights``
    matching, run once at compile): returns ``(leaf_of, fixed, treedef,
    template_leaves)`` where ``leaf_of[nid]`` is the flat leaf index a Const
    trains against and ``fixed[nid]`` holds every architecture constant."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    arrs = [np.asarray(v) for v in leaves]
    leaf_of: dict[int, int] = {}
    fixed: dict[int, jax.Array] = {}
    for nid, n in cg.graph.nodes.items():
        if n.op != "Const":
            continue
        c = np.asarray(n.const)
        matches = [i for i, a in enumerate(arrs)
                   if a.shape == c.shape and a.dtype == c.dtype
                   and np.array_equal(a, c)]
        # equal leaves (zero biases) are told apart by the array each
        # Const was traced from, when the template is fn's own params
        src = dict(n.params).get("src")
        own = [i for i in matches if id(leaves[i]) == src]
        if own:
            matches = own
        if not matches:
            fixed[nid] = jnp.asarray(c)
        elif len(matches) == 1:
            leaf_of[nid] = matches[0]
        else:
            raise ValueError(
                f"Const node {nid} matches {len(matches)} identical template "
                f"leaves — trainable binding is ambiguous")
    if not leaf_of:
        raise ValueError("no template leaf appears as a Const of the traced "
                         "graph — params do not parameterize fn")
    return leaf_of, fixed, treedef, leaves


# ---------------------------------------------------------------------------
# the differentiable block pipeline
# ---------------------------------------------------------------------------

def _region_unit_fn(cg, region):
    """Differentiable twin of ``executor._run_region``: identical operand
    assembly, but dispatched through the cached custom-vjp region call."""
    from repro.kernels.region import region_grad_fn
    plan, g = cg.plan, cg.graph
    cfg = cg.config
    spec = region.spec
    block, B = cfg.block, plan.batch
    out_info = tuple((g.nodes[o].shape[-1], str(np.dtype(g.nodes[o].dtype)))
                     for o in region.outputs)
    bias_ids = {s[4] for s in spec.steps if s[0] == "mm" and s[4] is not None}
    call = region_grad_fn(spec, out_info, cfg.bm)

    def run(res_env, env):
        stream = [env[nid] for nid in region.stream_inputs]
        n_rows = stream[0].shape[0] if stream else block
        for nid, cols in region.broadcast_inputs:
            a = _resident_val(plan, res_env, nid, block, B)
            stream.append(jnp.broadcast_to(a, (n_rows, cols)))
        rows = []
        for nid, cols in region.bcast_rows:
            a = _resident_val(plan, res_env, nid, block, B)
            if a.ndim >= 2:
                a = a[:1].reshape(1, a.shape[-1])
            elif a.ndim == 1:
                a = a[None, :]
            else:
                a = a.reshape(1, 1)
            rows.append(a)
        residents = []
        for nid in region.resident_inputs:
            a = res_env[nid]
            if nid in bias_ids and a.ndim == 2:
                a = a[0]
            residents.append(a)
        outs = call(*stream, *rows, *residents)
        return dict(zip(region.outputs, outs))

    return run


def _segment_unit_fn(cg, seg):
    """One segment through the per-node interpreter — pure jnp, so plain
    reverse-mode differentiates it (the CPU/default fit path)."""
    plan = cg.plan
    block, B = cg.config.block, plan.batch

    def run(res_env, env):
        out = _run_segment(plan, seg, INTERPRET, env, res_env, block, B)
        return {seg.output: out}

    return run


def _checkpointed(fnu):
    """Gradient checkpoint cut as a custom-vjp recompute: forward saves ONLY
    the unit's boundary inputs; backward replays the unit's forward under
    ``jax.vjp`` and applies the SAME pullback jaxpr plain autodiff would —
    recomputed residuals are deterministic replays of the saved ones, so
    cut-vs-buffer is bit-invariant (tests gate ``array_equal``), unlike
    ``jax.checkpoint`` whose rematerialized jaxpr XLA may fuse differently."""
    @jax.custom_vjp
    def wrapped(res_env, env):
        return fnu(res_env, env)

    def fwd(res_env, env):
        return fnu(res_env, env), (res_env, env)

    def bwd(saved, ct):
        _, pullback = jax.vjp(fnu, *saved)
        return pullback(ct)

    wrapped.defvjp(fwd, bwd)
    return wrapped


def _make_fit_block_fn(cg, checkpoints):
    """``f(res_env, xblk) -> streamed outs`` over the artifact's execution
    units, with a recompute boundary around each cut unit: its interior is
    rebuilt on the backward sweep from the boundary tensors alone."""
    plan, g = cg.plan, cg.graph
    units = _fit_units(cg)
    input_nodes = [g.nodes[i] for i in plan.inputs]
    streamed_outs = cg._streamed_outs
    cut = set(checkpoints)

    unit_fns = []
    for idx, (kind, u) in enumerate(units):
        fnu = (_region_unit_fn(cg, u) if kind == "region"
               else _segment_unit_fn(cg, u))
        needs = tuple(u.stream_inputs)
        if idx in cut:
            fnu = _checkpointed(fnu)
        unit_fns.append((fnu, needs))

    def block_fn(res_env, xblk):
        env = {n.id: xblk for n in input_nodes}
        for fnu, needs in unit_fns:
            sub = {nid: env[nid] for nid in needs if nid in env}
            env.update(fnu(res_env, sub))
        return tuple(env[o] for o in streamed_outs)

    return block_fn


def _fit_units(cg):
    """The execution-unit walk of the fit: under Pallas every region with
    a spec runs through the differentiable region kernel
    (``RegionPlan.fit_units``); the rest on the per-node interpreter."""
    if cg.region_plan is not None and cg.config.use_pallas:
        return cg.region_plan.fit_units()
    return [("seg", s) for s in cg.plan.segments]


# ---------------------------------------------------------------------------
# the artifact
# ---------------------------------------------------------------------------

@dataclass(eq=False)
class CompiledFit:
    """A cached fitting artifact: the serving ``CompiledGradient`` plus a
    streamed loss-gradient program over it.

    ``value_and_grad(params, coords, targets)`` returns the mean loss over
    ``coords`` and its gradient in the caller's params pytree — computed
    block-by-block with online accumulation, never materializing a per-grid
    activation tensor.  Jit the call (the fit engine does) for steady-state
    stepping."""
    cg: object
    loss: Objective
    checkpoints: tuple[int, ...]
    leaf_of: dict[int, int]
    fixed: dict[int, jax.Array]
    treedef: object
    template_leaves: list
    meta: dict = field(default_factory=dict, compare=False)

    def __post_init__(self):
        g = self.cg.graph
        plan = self.cg.plan
        self.in_features = g.nodes[plan.inputs[0]].shape[-1]
        self.out_features = g.nodes[g.outputs[0]].shape[-1]
        self._block_fn = _make_fit_block_fn(self.cg, self.checkpoints)
        self._resident_order = [
            (nid, g.nodes[nid]) for nid in plan.resident_order()]

    # -- identity ----------------------------------------------------------
    @property
    def order(self) -> int:
        return self.cg.order

    @property
    def config(self):
        return self.cg.config

    @property
    def signature(self) -> str:
        return self.cg.signature

    @property
    def n_trainable(self) -> int:
        return len({i for i in self.leaf_of.values()})

    # -- params plumbing ---------------------------------------------------
    def leaves_of(self, params) -> tuple:
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if treedef != self.treedef:
            raise ValueError(f"params treedef {treedef} != compiled "
                             f"{self.treedef}")
        return tuple(leaves)

    def unflatten(self, leaves):
        return jax.tree_util.tree_unflatten(self.treedef, list(leaves))

    def payload(self, params) -> dict[int, np.ndarray]:
        """``ArtifactStore.put_weights`` payload for a fitted params pytree:
        trained Consts from the leaves, architecture constants as-is."""
        leaves = self.leaves_of(params)
        out = {nid: np.asarray(leaves[i]) for nid, i in self.leaf_of.items()}
        out.update({nid: np.asarray(v) for nid, v in self.fixed.items()})
        return out

    # -- the streamed loss gradient ----------------------------------------
    def _res_env(self, leaves):
        """Rebuild the resident environment differentiably from the
        trainable leaves (the MultiINRArtifact recompute, under grad)."""
        env: dict[int, jax.Array] = {}
        for nid, n in self._resident_order:
            if n.op == "Const":
                i = self.leaf_of.get(nid)
                env[nid] = (jnp.asarray(leaves[i]) if i is not None
                            else self.fixed[nid])
            else:
                env[nid] = _eval_node(n, [env[i] for i in n.inputs])
        return env

    def _blocked(self, coords, targets):
        block = self.config.block
        N = coords.shape[0]
        cols = self.loss.target_cols(self.out_features, self.in_features)
        t = jnp.reshape(jnp.asarray(targets), (N, cols))
        pad = (-N) % block
        if pad:
            coords = jnp.pad(coords, ((0, pad), (0, 0)))
            t = jnp.pad(t, ((0, pad), (0, 0)))
        mask = (jnp.arange(N + pad) < N).astype(coords.dtype)
        nb = (N + pad) // block
        return (coords.reshape(nb, block, coords.shape[-1]),
                t.reshape(nb, block, cols),
                mask.reshape(nb, block), N)

    def value_and_grad(self, params, coords, targets):
        """Mean loss over the grid and its ∂/∂params — streamed: one block
        of activations live at a time, gradient partials accumulated in the
        scan carry, one normalization at the end."""
        leaves = self.leaves_of(params)
        loss, gleaves = self._stream_vg(leaves, coords, targets)
        grads = [jnp.zeros_like(l) for l in self.template_leaves]
        matched = sorted({i for i in self.leaf_of.values()})
        for i in matched:
            grads[i] = gleaves[i]
        return loss, self.unflatten(grads)

    def _stream_vg(self, leaves, coords, targets):
        """Flat-leaves core (what the K-batched engine vmaps): returns
        ``(mean loss, grad per leaf)``."""
        xb, yb, mb, N = self._blocked(coords, targets)
        C, D = self.out_features, self.in_features

        def block_loss(lv, xblk, yblk, mblk):
            res_env = self._res_env(lv)
            outs = self._block_fn(res_env, xblk)
            return jnp.sum(self.loss.row_loss(outs, yblk, C, D) * mblk)

        zeros = tuple(jnp.zeros_like(l) for l in leaves)

        def body(carry, inp):
            ls, gs = carry
            l, gl = jax.value_and_grad(block_loss)(leaves, *inp)
            return (ls + l, tuple(a + b for a, b in zip(gs, gl))), None

        init = (jnp.zeros((), jnp.float32), zeros)
        (ls, gs), _ = jax.lax.scan(body, init, (xb, yb, mb))
        n = jnp.asarray(N, jnp.float32)
        return ls / n, tuple(g / n for g in gs)

    # -- the memory model --------------------------------------------------
    def peak_bytes(self, n_rows: int | None = None) -> int:
        """Modeled peak fit memory.  ``n_rows=None`` — the STREAMED path:
        optimizer state (params, grads, Adam mu/nu) plus ONE block's
        backward-sweep buffering under the checkpoint cuts.  With
        ``n_rows`` — the whole-grid ``jax.grad`` baseline: every unit's
        activations buffered for EVERY row, no cuts."""
        plan, cfg = self.cg.plan, self.config
        units = _fit_units(self.cg)
        param_bytes = sum(np.asarray(l).nbytes for l in self.template_leaves)
        state = 4 * param_bytes            # params + grads + Adam mu/nu
        if n_rows is None:
            act = fit_backward_bytes(plan, units, cfg, self.checkpoints)
            rows = cfg.block
        else:
            act = n_rows * sum(unit_act_row_bytes(plan, k, u)
                               for k, u in units)
            rows = n_rows
        g = self.cg.graph
        io = rows * (np.dtype(g.nodes[plan.inputs[0]].dtype).itemsize
                     * self.in_features
                     + 4 * self.loss.target_cols(self.out_features,
                                                 self.in_features))
        return state + act + io

    def describe(self) -> str:
        units = _fit_units(self.cg)
        return (f"CompiledFit[{type(self.loss).__name__} order={self.order}] "
                f"{len(units)} units, {len(self.checkpoints)} checkpointed, "
                f"{self.n_trainable} trainable leaves, "
                f"peak_model={self.peak_bytes()}B")


# ---------------------------------------------------------------------------
# the front door (cache lives in core.pipeline next to its siblings)
# ---------------------------------------------------------------------------

def _resolve_checkpoints(cg, checkpoints):
    units = _fit_units(cg)
    if checkpoints == "auto":
        return plan_fit_checkpoints(cg.plan, units, cg.config)
    if checkpoints == "none":
        return ()
    if checkpoints == "all":
        return tuple(range(len(units)))
    return tuple(sorted(int(i) for i in checkpoints))


def compile_fit(fn, loss: Objective, order: int, example_coords, *,
                params, config=None, block=None, use_pallas=None,
                store=None, checkpoints="auto") -> CompiledFit:
    """Compile-or-hit the streamed fitting artifact for ``fn``'s order-n
    gradient pipeline under objective ``loss``.

    Delegates the heavy half to ``compile_gradient`` — same trace, same
    optimizer passes, same region schedule, same THREE-LEVEL lookup
    (in-process cache -> ArtifactStore -> trace+compile+persist) — then
    binds the ``params`` template to the graph's Const nodes and builds the
    streamed loss-gradient program.  Repeat calls with the same (artifact,
    loss, checkpoint policy) return the SAME ``CompiledFit``.

    ``checkpoints``: ``"auto"`` (the byte-model planner), ``"none"``,
    ``"all"``, or an explicit tuple of unit indices."""
    from repro.core import pipeline

    if not isinstance(loss, Objective):
        raise TypeError(f"loss must be a fit Objective, got {type(loss)}")
    if order < loss.min_order:
        raise ValueError(f"{type(loss).__name__} reads order-"
                         f"{loss.min_order} outputs; order={order} given")

    cg = pipeline.compile_gradient(fn, order, example_coords, config=config,
                                   block=block, use_pallas=use_pallas,
                                   store=store)
    if len(cg.plan.inputs) != 1:
        raise ValueError("compile_fit supports single-coordinate-input "
                         f"graphs; got {len(cg.plan.inputs)} inputs")
    if any(o in cg.plan.resident for o in cg.graph.outputs):
        raise ValueError("compile_fit requires every graph output to be "
                         "streamed (coordinate-dependent)")
    cuts = _resolve_checkpoints(cg, checkpoints)
    key = (cg, loss, cuts)
    hit = pipeline._FIT_CACHE.get(key)
    if hit is not None:
        hit.cg.cache_hits += 1
        return hit
    leaf_of, fixed, treedef, leaves = match_trainable(cg, params)
    cf = CompiledFit(cg=cg, loss=loss, checkpoints=cuts, leaf_of=leaf_of,
                     fixed=fixed, treedef=treedef, template_leaves=leaves)
    pipeline._INTERP_NODES.inc(sum(len(u.nodes) for kind, u in _fit_units(cg)
                                   if kind == "seg"), path="fit")
    pipeline._FIT_CACHE[key] = cf
    return cf
