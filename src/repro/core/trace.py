"""Computation-graph extraction (paper Sec. 3.2.2, contribution 2).

The paper walks PyTorch's autograd graph; the JAX-native equivalent is to
trace the (possibly nested-gradient) function with ``jax.make_jaxpr`` and
convert the jaxpr to our ComputeGraph IR, inlining call primitives
(pjit/remat/custom_jvp) so the raw chain-rule redundancy is visible to the
optimization passes — exactly the redundancy the paper's de-duplication pass
removes (their Table III).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.core.graph import ComputeGraph

# jaxpr primitive -> IR op name.  Names follow the paper (Mm, T, Permute, ...)
PRIM_MAP = {
    "dot_general": "Mm",
    "transpose": "Permute",
    "sin": "Sin",
    "cos": "Cos",
    "mul": "Mul",
    "add": "Add",
    "add_any": "Add",           # AD cotangent accumulation
    "sub": "Sub",
    "div": "Div",
    "neg": "Neg",
    "exp": "Exp",
    "log": "Log",
    "tanh": "Tanh",
    "pow": "Pow",
    "integer_pow": "IntPow",
    "broadcast_in_dim": "Broadcast",
    "reduce_sum": "Sum",
    "reduce_max": "Max",
    "reshape": "Reshape",
    "convert_element_type": "Convert",
    "squeeze": "Reshape",
    "expand_dims": "Reshape",
    "select_n": "Select",
    "max": "Maximum",
    "min": "Minimum",
    "stop_gradient": "Identity",
    "copy": "Identity",
    "slice": "Slice",
    "pad": "Pad",
    "concatenate": "Concat",
    "dynamic_slice": "DynSlice",
    "dynamic_update_slice": "DynUpdate",
    "iota": "Iota",
    "rsqrt": "Rsqrt",
    "sqrt": "Sqrt",
    "abs": "Abs",
    "sign": "Sign",
    "logistic": "Sigmoid",
    "erf": "Erf",
    "log1p": "Log1p",
    # comparisons: boolean masks, consumed by Select (the derivative rules
    # of max, abs and logaddexp, and the isnan / isinf tests of softplus)
    "eq": "Eq",
    "ne": "Ne",
    "lt": "Lt",
    "le": "Le",
    "gt": "Gt",
    "ge": "Ge",
}

_CALL_PRIMS = {"pjit", "closed_call", "core_call", "xla_call", "remat", "checkpoint",
               "custom_jvp_call", "custom_vjp_call", "custom_jvp_call_jaxpr",
               "custom_vjp_call_jaxpr", "jit"}

_STATIC_PARAM_KEYS = ("dimension_numbers", "permutation", "axes", "padding_config",
                      "broadcast_dimensions", "new_sizes", "dimensions",
                      "shape", "start_indices", "limit_indices", "strides",
                      "y", "dimension", "new_dtype")


def _norm(v):
    """Normalize static params to plain hashable Python values (numpy 2.x
    scalars repr as np.int64(1), which would break emitted source)."""
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, (np.bool_,)):
        return bool(v)
    if isinstance(v, np.dtype):
        return str(v)
    if isinstance(v, (tuple, list)):
        return tuple(_norm(x) for x in v)
    try:
        hash(v)
    except TypeError:
        return str(v)
    return v


def _params_tuple(prim, params) -> tuple:
    out = []
    for k in _STATIC_PARAM_KEYS:
        if k in params:
            out.append((k, _norm(params[k])))
    return tuple(out)


def _inner_jaxpr(params):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in params:
            j = params[key]
            return j.jaxpr if hasattr(j, "jaxpr") else j, getattr(j, "consts", [])
    return None, []


# monotonic tracer-invocation counter: the serving layer's warm-restore
# guarantee is "zero tracer invocations", and tests/CI assert it by delta
TRACE_CALLS = 0


def trace_count() -> int:
    return TRACE_CALLS


def extract_graph(fn, *example_args, flatten_outputs=True) -> ComputeGraph:
    """Trace ``fn`` at the given example args and convert to ComputeGraph."""
    global TRACE_CALLS
    TRACE_CALLS += 1
    closed = jax.make_jaxpr(fn)(*example_args)
    g = ComputeGraph()
    env: dict = {}

    def aval_of(var):
        return var.aval

    def read(var, consts_env):
        if isinstance(var, jcore.Literal):
            arr = np.asarray(var.val)
            return g.add("Const", arr.shape, arr.dtype, const=arr)
        return consts_env[var]

    def walk(jaxpr, consts_env):
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            inner, inner_consts = _inner_jaxpr(eqn.params)
            if inner is not None:
                # inline call primitive: bind consts + args into inner env
                sub_env = {}
                in_ids = [read(v, consts_env) for v in eqn.invars]
                # consts of ClosedJaxpr come first as literals
                for cv, cval in zip(inner.constvars, inner_consts):
                    arr = np.asarray(cval)
                    sub_env[cv] = g.add("Const", arr.shape, arr.dtype, const=arr)
                for v, nid in zip(inner.invars, in_ids):
                    sub_env[v] = nid
                walk(inner, sub_env)
                for ov, iv in zip(eqn.outvars, inner.outvars):
                    consts_env[ov] = read(iv, sub_env)
                continue

            op = PRIM_MAP.get(prim)
            in_ids = [read(v, consts_env) for v in eqn.invars]

            # --- canonicalize dot_general into (Permute?) + Mm, torch-style.
            # PyTorch autograd graphs show explicit T nodes on matmul
            # backward (dy @ W.T, x.T @ dy); jaxpr hides them inside
            # dimension_numbers, so we re-materialize them for the passes.
            if prim == "dot_general":
                (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
                lhs_aval = eqn.invars[0].aval
                rhs_aval = eqn.invars[1].aval
                if (not lb and not rb and len(lhs_aval.shape) == 2
                        and len(rhs_aval.shape) == 2
                        and len(lc) == 1 and len(rc) == 1):
                    lhs_id, rhs_id = in_ids
                    if lc[0] == 0:
                        ls = lhs_aval.shape
                        lhs_id = g.add("Permute", (ls[1], ls[0]), lhs_aval.dtype,
                                       (lhs_id,), (("permutation", (1, 0)),))
                    if rc[0] == 1:
                        rs = rhs_aval.shape
                        rhs_id = g.add("Permute", (rs[1], rs[0]), rhs_aval.dtype,
                                       (rhs_id,), (("permutation", (1, 0)),))
                    ov = eqn.outvars[0]
                    nid = g.add("Mm", ov.aval.shape, ov.aval.dtype,
                                (lhs_id, rhs_id))
                    consts_env[ov] = nid
                    continue

            # --- split (the transpose of concatenate) becomes one Slice per
            # output, so the passes, the executor and the kernels see the
            # op they already know
            if prim == "split":
                axis = eqn.params["axis"]
                shape = eqn.invars[0].aval.shape
                lo = 0
                for ov, size in zip(eqn.outvars, eqn.params["sizes"]):
                    start = [0] * len(shape)
                    limit = list(shape)
                    start[axis], limit[axis] = lo, lo + size
                    lo += size
                    consts_env[ov] = g.add(
                        "Slice", ov.aval.shape, ov.aval.dtype, in_ids,
                        (("start_indices", tuple(start)),
                         ("limit_indices", tuple(limit)), ("strides", None)))
                continue

            if op is None:
                op = prim[:1].upper() + prim[1:]     # passthrough with raw name
            if len(eqn.outvars) == 1:
                ov = eqn.outvars[0]
                nid = g.add(op, ov.aval.shape, ov.aval.dtype, in_ids,
                            _params_tuple(prim, eqn.params))
                consts_env[ov] = nid
            else:
                for k, ov in enumerate(eqn.outvars):
                    nid = g.add(f"{op}#{k}", ov.aval.shape, ov.aval.dtype,
                                in_ids, _params_tuple(prim, eqn.params) + (("out", k),))
                    consts_env[ov] = nid

    top_env: dict = {}
    for i, v in enumerate(closed.jaxpr.invars):
        top_env[v] = g.add("Input", v.aval.shape, v.aval.dtype, params=(("idx", i),))
    for cv, cval in zip(closed.jaxpr.constvars, closed.consts):
        # a closed-over array (a weight) keeps its source's identity: two
        # weights that happen to hold equal values (the zero biases of a
        # freshly initialised net) stay two Consts, one per parameter
        arr = np.asarray(cval)
        top_env[cv] = g.add("Const", arr.shape, arr.dtype,
                            params=(("src", id(cval)),), const=arr)
    walk(closed.jaxpr, top_env)
    g.outputs = [read(v, top_env) for v in closed.jaxpr.outvars]
    g.prune_dead()
    return g
