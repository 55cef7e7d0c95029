"""Sharding policy: logical axes -> mesh PartitionSpecs.

Every parameter in the model zoo is declared with *logical* axis names
(e.g. ``("vocab", "embed")``).  This module maps logical names to mesh axes
(TP over "model", FSDP over the data axes, EP over "model" for experts) with
divisibility checks: a dim is only sharded if the mesh axis size divides it,
otherwise we fall back to the next candidate or replicate.  This is what lets
one policy serve 10 architectures with odd head counts / vocab sizes.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules below
    place arrays and let jit propagate, which Explicit axes would refuse."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


# Candidate mesh axes per logical axis, in preference order.  "fsdp" is a
# pseudo-axis that expands to the batch axes of the mesh (("pod","data") on
# the multi-pod mesh, ("data",) on a single pod).
LOGICAL_RULES: dict[str, tuple[str, ...]] = {
    # embedding / unembedding
    "vocab": ("model",),
    "embed": ("fsdp",),          # d_model dim of embed table -> FSDP
    # attention
    "q_dim": ("model",),         # fused n_heads*head_dim
    "kv_dim": ("model",),        # fused n_kv*head_dim
    "o_in": ("model",),          # Wo input dim (row-parallel)
    "attn_fsdp": ("fsdp",),      # d_model dim of attention projections
    # mlp
    "ff": ("model",),
    "mlp_fsdp": ("fsdp",),
    # moe
    "experts": ("model",),       # expert parallelism
    "expert_ff": (),             # inner expert dim: keep whole per device
    "expert_fsdp": ("fsdp",),
    # mamba
    "ssm_inner": ("model",),
    "ssm_state": (),
    "ssm_heads": ("model",),
    "ssm_fsdp": ("fsdp",),
    # never shard
    "stack": (),                 # scanned-layer leading dim
    "tiny": (),                  # norms, biases, per-head scalars
    "conv_w": (),
}

# Activation logical axes
ACT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("fsdp",),
    # the multi-INR K axis (serve/multi_inr.py): stacked weight payloads of
    # a fleet of resident INRs — the large tensor at fleet scale.  Sharded
    # across the data axes first (each INR's weights are independent), the
    # model axis as fallback; rows stay per-shard-local (DESIGN.md §8).
    "inr": ("fsdp", "model"),
    "seq": (),                   # overridden to ("model",) under seq parallelism
    "act_embed": (),
    "act_heads": ("model",),
    "act_kv_heads": ("model",),
    "act_vocab": ("model",),
    "head_dim": (),
    "image": (),
    # KV / SSM cache axes
    "stack": (),
    "seq_kv": (),                # default: cache seq unsharded
    "seq_shard": ("model",),     # fallback when kv heads don't divide |model|
    "ssm_heads": ("model",),
    "ssm_conv": ("model",),
}


@dataclass(frozen=True)
class ShardingPolicy:
    """Resolves logical axes against a concrete mesh."""
    mesh: Mesh
    seq_parallel: bool = False           # shard activations' seq dim over model
    extra_rules: dict | None = None      # overrides for perf experiments

    def _mesh_axes(self, logical: str, rules: dict[str, tuple[str, ...]]):
        if self.extra_rules and logical in self.extra_rules:
            cands = self.extra_rules[logical]
        else:
            cands = rules.get(logical, ())
        out: list = []
        for c in cands:
            if c == "fsdp":
                fsdp = tuple(a for a in ("pod", "data") if a in self.mesh.shape)
                if fsdp:
                    out.append(fsdp if len(fsdp) > 1 else fsdp[0])
            elif c in self.mesh.shape:
                out.append(c)
        return out

    def _axis_size(self, entry) -> int:
        if isinstance(entry, tuple):
            return math.prod(self.mesh.shape[a] for a in entry)
        return self.mesh.shape[entry]

    def spec(self, shape: tuple[int, ...], logical: tuple[str | None, ...],
             rules=None) -> P:
        """Build a PartitionSpec: shard each dim by the first candidate mesh
        axis (or axis tuple) that divides it and is not already used."""
        rules = rules or LOGICAL_RULES
        used: set[str] = set()
        parts: list = []
        for dim, name in zip(shape, logical):
            choice = None
            if name is not None:
                for cand in self._mesh_axes(name, rules):
                    flat = cand if isinstance(cand, tuple) else (cand,)
                    if used & set(flat):
                        continue
                    if dim % self._axis_size(cand) == 0:
                        choice = cand
                        used.update(flat)
                        break
            parts.append(choice)
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def act_spec(self, shape, logical) -> P:
        rules = dict(ACT_RULES)
        if self.seq_parallel:
            rules["seq"] = ("model",)
        return self.spec(shape, logical, rules)

    def named(self, shape, logical, *, act=False) -> NamedSharding:
        s = self.act_spec(shape, logical) if act else self.spec(shape, logical)
        return NamedSharding(self.mesh, s)


def tree_specs(policy: ShardingPolicy, template) -> "jax.tree_util.PyTreeDef":
    """Map a ParamSpec template tree -> PartitionSpec tree."""
    from repro.models.template import ParamSpec  # local import, avoid cycle
    return jax.tree.map(
        lambda ps: policy.spec(ps.shape, ps.logical),
        template,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def tree_shardings(policy: ShardingPolicy, template):
    from repro.models.template import ParamSpec
    return jax.tree.map(
        lambda ps: NamedSharding(policy.mesh, policy.spec(ps.shape, ps.logical)),
        template,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )
