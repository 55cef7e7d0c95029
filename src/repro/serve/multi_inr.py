"""Multi-INR batched serving: many weight sets through ONE compiled plan.

A CompiledGradient's plan, dispatch decisions, and block geometry are
WEIGHT-INDEPENDENT — only the resident environment (the Const leaves and
everything derived from them) changes between two INRs of the same
architecture.  So K INRs can share one artifact by lifting the residents to
a stacked leading axis and ``vmap``-ing the per-block pipeline over it:

    block_fn(res, xblk)            # the artifact's resident-parameterized
                                   # per-block pipeline
    vmap(block_fn, (0, 0))         # res leaves [K, ...], coords [K, block, d]

which is the amortize-one-plan-over-many-signals structure PatchINR argues
scalable INR inference needs.  Per-INR derived residents are recomputed once
at construction (cheap: a handful of small matmuls per weight set — never a
re-trace), then stacked; serving pads every INR's query rows to a common
block multiple and streams [n_blocks, K, block, ...] through one jitted
``lax.map``-of-``vmap``.

Weight payloads map Const node id -> array.  ``bind_weights`` derives a new
INR's payload from a params pytree WITHOUT compiling it, by matching the
base artifact's Const values against the template params (random init makes
the match unique; shared literals — w0 scalars, reverse-mode seeds — match
nothing and stay shared).

RESIDENT DOUBLE BUFFERING (DESIGN.md §7).  The vmap path interleaves every
lane's math inside one XLA program, which leaves the per-lane resident
weight swap implicit.  ``resident_double_buffer=True`` instead serves the
K lanes through ``kernels.region.region_call_stacked`` when the whole
pipeline is region megakernels: ONE pallas_call with grid (lane, row tile),
each lane's residents one grid-block on the slow axis — the Pallas pipeline
prefetches lane k+1's weights into VMEM while lane k computes its last row
tile, overlapping the weight swap with compute.  Numerics are the region
megakernel's (bit-identical per lane to the base artifact's region path);
the flag silently falls back to the vmap path when the plan has non-region
units, streamed-broadcast extras, or a K-sharded mesh
(``.double_buffered`` reports which path serves).

K-AXIS SHARDING (DESIGN.md §8).  At fleet scale the stacked residents ARE
the large tensor — thousands of weight sets vs one small query block — so
``MultiINRArtifact(..., sharding=policy)`` shards the stacked [K] axis
across the policy's mesh: every resident leaf is placed K-sharded
(``ACT_RULES["inr"]``), query batches are placed with the SAME K axis
sharded and the rows axis per-shard-local, and ``shard_map`` runs the
vmapped block pipeline over each device's own lanes with no cross-shard
collective in the hot loop (each INR's serve is independent; the SPMD
partitioner cannot split a Mosaic kernel, so the split is explicit).  When
K does not divide the mesh, the policy's divisibility fallback replicates —
identical numerics, no sharding.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import _eval_node


def pad_rows(c, n_pad: int):
    """Pad [N, ...] query rows out to ``n_pad`` by replicating the edge row
    (zeros when N == 0 — there is no edge to replicate; either way the
    padding never reaches a caller, outputs are sliced back to N)."""
    n = c.shape[0]
    if n >= n_pad:
        return c
    if n == 0:
        return jnp.zeros((n_pad,) + tuple(c.shape[1:]), c.dtype)
    edge = jnp.broadcast_to(c[-1:], (n_pad - n,) + c.shape[1:])
    return jnp.concatenate([c, edge])


def const_payload(cg) -> dict[int, np.ndarray]:
    """The artifact's weight payload: every Const node's value, keyed by
    node id (the same keying the ArtifactStore persists)."""
    return {nid: np.asarray(n.const)
            for nid, n in cg.graph.nodes.items() if n.op == "Const"}


def bind_weights(cg, template_params, new_params) -> dict[int, np.ndarray]:
    """Payload for a NEW weight set of ``cg``'s architecture, derived from a
    params pytree — no trace, no compile.

    ``template_params`` must be the exact pytree ``cg`` was compiled from
    (its leaves appear verbatim as Const nodes); ``new_params`` must share
    its treedef and leaf shapes/dtypes.  Each Const node is matched to the
    template leaf it equals and replaced by the corresponding new leaf;
    Consts matching no leaf (w0 scalars, cotangent seeds, literals) are
    architecture constants and stay shared.  Ambiguous matches (two equal
    template leaves whose new values differ) raise rather than guess."""
    t_leaves, t_def = jax.tree_util.tree_flatten(template_params)
    n_leaves, n_def = jax.tree_util.tree_flatten(new_params)
    if t_def != n_def:
        raise ValueError(f"new_params treedef {n_def} != template {t_def}")
    t_arrs = [np.asarray(v) for v in t_leaves]
    n_arrs = [np.asarray(v) for v in n_leaves]
    for i, (a, b) in enumerate(zip(t_arrs, n_arrs)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {i}: new {b.shape}/{b.dtype} != "
                             f"template {a.shape}/{a.dtype}")

    payload: dict[int, np.ndarray] = {}
    for nid, n in cg.graph.nodes.items():
        if n.op != "Const":
            continue
        c = np.asarray(n.const)
        matches = [i for i, a in enumerate(t_arrs)
                   if a.shape == c.shape and a.dtype == c.dtype
                   and np.array_equal(a, c)]
        if not matches:
            payload[nid] = c                      # shared literal
            continue
        cands = {n_arrs[i].tobytes() for i in matches}
        if len(cands) > 1:
            raise ValueError(
                f"Const node {nid} matches {len(matches)} template leaves "
                f"with differing replacement values — weight binding is "
                f"ambiguous (identical template leaves)")
        payload[nid] = n_arrs[matches[0]]
    return payload


class MultiINRArtifact:
    """K INRs of one architecture served through one compiled artifact.

    ``base`` supplies the plan/config/dispatch (and the graph's shared
    literals); ``payloads`` is one {Const node id: array} weight payload per
    INR (see ``bind_weights`` / ``ArtifactStore.load_weights``).  Residents
    are recomputed per payload and stacked on a leading [K] axis; execution
    is the base artifact's resident-parameterized block pipeline vmapped
    over that axis.
    """

    def __init__(self, base, payloads, inr_ids=None, *, sharding=None,
                 resident_double_buffer: bool = False):
        if not payloads:
            raise ValueError("need at least one weight payload")
        self.base = base
        self.sharding = sharding       # distributed.sharding.ShardingPolicy
        self.inr_ids = (list(inr_ids) if inr_ids is not None
                        else list(range(len(payloads))))
        if len(self.inr_ids) != len(payloads):
            raise ValueError("inr_ids and payloads disagree in length")
        g, plan = base.graph, base.plan
        const_ids = {nid for nid, n in g.nodes.items() if n.op == "Const"}

        per_inr: list[dict] = []
        for payload in payloads:
            missing = const_ids - {int(k) for k in payload}
            if missing:
                raise ValueError(f"payload missing Const nodes "
                                 f"{sorted(missing)}")
            res: dict[int, jax.Array] = {}
            for nid in plan.resident_order():
                n = g.nodes[nid]
                if n.op == "Const":
                    res[nid] = jnp.asarray(payload[nid])
                else:
                    res[nid] = _eval_node(n, [res[i] for i in n.inputs])
            per_inr.append(res)
        # stack: resident leaves gain the [K] axis the block fn is vmapped over
        self.residents = {nid: jnp.stack([r[nid] for r in per_inr])
                          for nid in per_inr[0]}
        # K-axis sharding: place every stacked resident once, so the weight
        # fleet lives sharded from the start
        self._k_sharding = self._resolve_k_sharding()
        if self._k_sharding is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            mesh, ax = self._k_sharding
            self.residents = {
                nid: jax.device_put(v, NamedSharding(mesh, P(ax)))
                for nid, v in self.residents.items()}
        self.double_buffered = (bool(resident_double_buffer)
                                and self._stacked_applicable())
        # the stacked residents enter as ARGUMENTS, not closure constants:
        # constants would bake every lane's weights into the executable (on
        # every device, whatever the K sharding) and recompile per stack
        self._serve_fn = jax.jit(self._make_serve_stacked()
                                 if self.double_buffered
                                 else self._make_serve())

    def _serve(self, xb):
        return self._serve_fn(self.residents, xb)

    def _resolve_k_sharding(self):
        """(mesh, k_axis) when the policy shards the K axis, else None (no
        policy, single device, or K not divisible -> replicate)."""
        if self.sharding is None:
            return None
        from jax.sharding import PartitionSpec as P
        spec = self.sharding.act_spec((self.n_inrs,), ("inr",))
        if spec == P():
            return None
        return self.sharding.mesh, spec[0]

    @property
    def k_sharded(self) -> bool:
        return self._k_sharding is not None

    def place_batch(self, xb):
        """Place a [nb, K, block, ...] block batch to match the residents:
        K axis sharded, the block (rows) axis per-shard-local."""
        if self._k_sharding is None:
            return xb
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh, ax = self._k_sharding
        return jax.device_put(xb, NamedSharding(mesh, P(None, ax)))

    @property
    def n_inrs(self) -> int:
        return len(self.inr_ids)

    def _make_serve(self):
        vblock = jax.vmap(self.base.resident_block_fn(),
                          in_axes=(0,) + (0,) * len(self.base.plan.inputs))

        def serve(res, xb):            # [n_blocks, K, block, ...features]
            return jax.lax.map(lambda b: vblock(res, b), xb)
        if self._k_sharding is None:
            return serve
        # Mosaic kernels cannot be split by the SPMD partitioner, so a
        # sharded fleet runs under shard_map: each device serves its own K/n
        # lanes against its own slice of the stacked residents
        from jax.sharding import PartitionSpec as P
        mesh, ax = self._k_sharding
        return jax.shard_map(serve, mesh=mesh, in_specs=(P(ax), P(None, ax)),
                             out_specs=P(None, ax), check_vma=False)

    def _stacked_applicable(self) -> bool:
        """True when the whole pipeline can serve through the K-stacked
        region megakernel: every unit a fused region with no streamed-
        broadcast extras, single coordinate input, Pallas dispatch on, no
        K-sharded mesh (a sharded fleet keeps the SPMD vmap path)."""
        base = self.base
        rp = getattr(base, "region_plan", None)
        if (rp is None or not base.config.use_pallas
                or len(base.plan.inputs) != 1
                or self._k_sharding is not None):
            return False
        units = rp.units()
        return bool(units) and all(
            kind == "region" and not u.broadcast_inputs
            for kind, u in units)

    def _make_serve_stacked(self):
        """The resident-double-buffered serve: the region pipeline runs as
        ``region_call_stacked`` over all K lanes — grid (lane, row tile),
        lane k+1's resident weights DMA'd while lane k computes (see
        ``kernels.region``).  Same [nb, K, block, ...] chunk contract as
        the vmap path."""
        from repro.kernels.region import region_call_stacked
        base = self.base
        g, plan = base.graph, base.plan
        cfg = base.config
        K = self.n_inrs
        B = plan.batch
        input_id = plan.inputs[0]
        regions = [u for _, u in base.region_plan.units()]
        streamed = self.streamed_outputs()

        def stacked_row(residents, nid):
            # one [K, 1, C] row per row-const extra (cf. executor's
            # per-lane [1, C] conversion)
            a = residents[nid]                     # [K, ...per-lane]
            if nid in plan.rowconst and a.ndim >= 2 and a.shape[1:2] == (B,):
                a = a[:, :1]
            if a.ndim >= 3:
                return a[:, :1].reshape(K, 1, a.shape[-1])
            if a.ndim == 2:
                return a[:, None, :]
            return a.reshape(K, 1, 1)

        def serve(residents, xb):      # [n_blocks, K, block, ...features]
            nb, _, block = xb.shape[:3]
            rows = nb * block
            env = {input_id: jnp.moveaxis(xb, 1, 0).reshape(
                K, rows, *xb.shape[3:])}
            for region in regions:
                spec = region.spec
                stream = [env[nid] for nid in region.stream_inputs]
                row_args = [stacked_row(residents, nid)
                            for nid, _ in region.bcast_rows]
                bias_ids = {s[4] for s in spec.steps
                            if s[0] == "mm" and s[4] is not None}
                res_args = []
                for nid in region.resident_inputs:
                    a = residents[nid]
                    if nid in bias_ids and a.ndim == 3:
                        a = a[:, 0]    # per-lane (1,N)/(B,N) bias -> (N,)
                    res_args.append(a)
                out_info = tuple((g.nodes[o].shape[-1], g.nodes[o].dtype)
                                 for o in region.outputs)
                outs = region_call_stacked(spec, stream, row_args, res_args,
                                           out_info, bm=cfg.bm)
                for nid, o in zip(region.outputs, outs):
                    env[nid] = o       # [K, rows, C]
            result = []
            for o in streamed:
                v = env[o]
                v = jnp.moveaxis(
                    v.reshape(K, nb, block, *v.shape[2:]), 0, 1)
                result.append(v)
            return tuple(result)
        return serve

    def apply_chunk(self, xb):
        """One jitted chunk step over an already-blocked batch: ``xb`` is
        [n_blocks, K, block, ...features]; returns the streamed outputs,
        each [n_blocks, K, block, ...].  The multi-INR analogue of
        ``CompiledGradient.apply_chunk`` — what the async engine's
        continuous-batching loop dispatches; shape-stable chunks (a fixed
        ``chunk_blocks`` x K) hit one compiled trace."""
        return self._serve(self.place_batch(xb))

    def resident_output(self, o: int, n: int):
        """A resident output for ``n`` rows, leading [K] axis."""
        return self._resident_output(o, n)

    def streamed_outputs(self) -> list[int]:
        return [o for o in self.base.graph.outputs
                if o not in self.base.plan.resident]

    def apply_batched(self, coords):
        """Serve every INR's queries in one batched streaming pass.

        ``coords`` is [K, N, ...features] (row k for INR k) or
        [N, ...features] (the same queries broadcast to all K).  N is padded
        to a block multiple (edge rows replicated; padding never reaches the
        caller) and [n_blocks, K, block, ...] streams through one jitted
        ``lax.map`` of the vmapped block pipeline.  Returns the same output
        tuple as ``base.apply_batched`` with a leading [K] axis.  Distinct
        padded block counts jit-cache separately (the serving engine keeps
        request batches shape-stable)."""
        base = self.base
        if len(base.plan.inputs) != 1:
            raise ValueError("multi-INR serving supports single-input "
                             "(coordinate) pipelines")
        coords = jnp.asarray(coords)
        feat_rank = len(base.graph.nodes[base.plan.inputs[0]].shape) - 1
        if coords.ndim == 1 + feat_rank:          # [N, ...] -> broadcast
            coords = jnp.broadcast_to(coords[None],
                                      (self.n_inrs,) + coords.shape)
        K, n = coords.shape[0], coords.shape[1]
        if K != self.n_inrs:
            raise ValueError(f"coords carry {K} INRs, artifact has "
                             f"{self.n_inrs}")
        block = base.config.block
        if n == 0:
            return tuple(
                self._resident_output(o, 0) if o in base.plan.resident
                else jnp.zeros((K, 0) + tuple(base.graph.nodes[o].shape[1:]),
                               base.graph.nodes[o].dtype)
                for o in base.graph.outputs)
        pad = (-n) % block
        if pad:
            edge = jnp.broadcast_to(coords[:, -1:],
                                    (K, pad) + coords.shape[2:])
            coords = jnp.concatenate([coords, edge], axis=1)
        nb = coords.shape[1] // block
        xb = jnp.moveaxis(
            coords.reshape(K, nb, block, *coords.shape[2:]), 0, 1)
        outs = self._serve(self.place_batch(xb))   # each [nb, K, block, ...]
        streamed = iter(
            jnp.moveaxis(o, 0, 1).reshape(K, nb * block, *o.shape[3:])[:, :n]
            for o in outs)
        return tuple(self._resident_output(o, n) if o in base.plan.resident
                     else next(streamed) for o in base.graph.outputs)

    def _resident_output(self, o: int, n: int):
        v = self.residents[o]                # [K, ...]
        B = self.base.plan.batch
        if (o in self.base.plan.rowconst and v.ndim > 1
                and v.shape[1:2] == (B,)):
            # row-constant resident output: one row serves any batch size
            v = jnp.broadcast_to(v[:, :1], (v.shape[0], n) + v.shape[2:])
        return v

    @classmethod
    def from_store(cls, store, signature: str, inr_ids, *, sharding=None):
        """Build from persisted weight sets: one ``load`` for the base
        artifact (no trace) plus one weight-payload read per INR."""
        inr_ids = list(inr_ids)
        if not inr_ids:
            raise ValueError("need at least one inr_id")
        base = store.load(signature, inr_id=inr_ids[0])
        payloads = [store.load_weights(signature, i) for i in inr_ids]
        return cls(base, payloads, inr_ids, sharding=sharding)

    def describe(self) -> str:
        shard = ""
        if self._k_sharding is not None:
            mesh, ax = self._k_sharding
            n = math.prod(mesh.shape[a] for a in
                          (ax if isinstance(ax, tuple) else (ax,)))
            shard = f", K sharded {n}-way over {ax!r}"
        dbuf = (", resident double-buffered (stacked region lanes)"
                if self.double_buffered else "")
        return (f"MultiINRArtifact: {self.n_inrs} INRs x "
                f"[{self.base.config.describe()}], "
                f"{len(self.residents)} stacked residents{shard}{dbuf}, "
                f"signature {self.base.signature}")
