"""IGR's Softplus SDF network on the normal path, and the step kinds it
brought to the kernels (comparisons, select, log1p).

Everything is checked against plain ``jax.numpy`` on seeded weights at
8 layers x 32 wide, skip at layer 4, beta = 100: the towers of orders 0-2
through ``compile_gradient(...).apply_batched``, ``compile_fit`` under
``IGRLoss``, and a few ``fit`` steps.  Each new chain step runs in
``fused_chain`` and in ``region_call`` (forward) and through the region's
backward kernel (``region_grad_fn``) against the per-node interpreter;
a fused_chain has no backward of its own, the fit differentiates only the
region kernel.  Off a TPU every kernel runs in Pallas interpret mode.

Tolerances are scaled errors, ``max|got - want| / max|want|``.  Each says
what it covers, and ``test_tolerances_catch_a_high_precision_dot`` shows
that a reference whose dots run one precision step down (three bf16
passes, the TPU's ``high``; emulated here, where the CPU computes every
float32 dot in full) falls outside them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pipeline as P
from repro.core.config import HardwareConfig
from repro.core.executor import _eval_node, reference_executor
from repro.core.graph import ComputeGraph
from repro.fit import IGRLoss, compile_fit, fit
from repro.fit.compile import _fit_units
from repro.inr.igr import IGRConfig, igr_fn, igr_init
from repro.kernels.fused_chain import build_chain_spec, fused_chain
from repro.kernels.region import RegionKernelSpec, region_call, region_grad_fn
from repro.obs.metrics import REGISTRY

CFG = IGRConfig(hidden_features=32)
PALLAS = HardwareConfig(use_pallas=True)
INTERPRETED = HardwareConfig(use_pallas=False)

# Program against plain jnp, per order.  The program and the reference run
# the same float32 operations in another association (8-row blocks, the
# region kernels' fused chains, scale by 1/beta for a division): a few
# ulps, grown by each derivative's beta = 100 factors.  Measured 3.8e-7,
# 6.9e-7 and 3.1e-6 for orders 0, 1 and 2; the reference with three-pass
# bf16 dots reads 6.0e-6, 2.4e-5 and 9.8e-5.
TOL = {0: 2e-6, 1: 5e-6, 2: 2e-5}
# the fit's gradient, worst leaf by norm: the order-1 tower's rounding
# taken through one more reverse pass (measured 6.0e-6; three-pass bf16
# dots 1.6e-4)
TOL_GRAD = 2e-5
# a step kind against the interpreter: the same jnp operations on the same
# float32 values, so bitwise, except log1p's derivative 1 / (1 + x),
# which XLA may fuse into a reciprocal-multiply: 2 ulps
TOL_STEP = 2.5e-7


@pytest.fixture(autouse=True)
def fresh_cache():
    P.clear_compile_cache()
    yield
    P.clear_compile_cache()


@pytest.fixture(scope="module")
def net():
    """Seeded weights with every bias drawn, so no two leaves are equal
    and every softplus sits near its bend."""
    params = igr_init(CFG, jax.random.PRNGKey(0))
    keys = jax.random.split(jax.random.PRNGKey(1), len(params))
    params = [{"w": p["w"], "b": 0.05 * jax.random.normal(k, p["b"].shape)}
              for p, k in zip(params, keys)]
    return params, igr_fn(CFG, params)


def _coords(n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).uniform(-1, 1, (n, 3)),
                       jnp.float32)


def _scaled(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# --------------------------------------------------------------------------
# the plain reference: ImplicitNet in jax.numpy, its tower and IGR's loss
# --------------------------------------------------------------------------

def _dot_highest(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _plain(params, x, dot=_dot_highest):
    h = x
    for i, layer in enumerate(params):
        if i == 4:
            h = jnp.concatenate([h, x], -1) / math.sqrt(2.0)
        h = dot(h, layer["w"]) + layer["b"]
        if i < len(params) - 1:
            h = jnp.logaddexp(100.0 * h, 0.0) / 100.0
    return h


def _plain_tower(params, x, order, dot=_dot_highest):
    """y, then dy/dx, then d(dy/dx_i)/dx per i: the served layout."""
    def f(z):
        return _plain(params, z, dot)
    outs = [f(x)]
    level = [lambda z: f(z)[:, 0].sum()]
    for _ in range(order):
        grads = [jax.grad(s) for s in level]
        outs.extend(g(x) for g in grads)
        level = [(lambda z, g=g, i=i: g(z)[:, i].sum())
                 for g in grads for i in range(3)]
    return tuple(outs)


def _plain_loss(params, x, t, loss: IGRLoss, dot=_dot_highest):
    y, dy = _plain_tower(params, x, 1, dot)
    s = jnp.sum(t * t, -1)
    surface = jnp.abs(y[:, 0]) + jnp.linalg.norm(dy - t, axis=-1)
    eikonal = (jnp.linalg.norm(dy, axis=-1) - 1.0) ** 2
    return jnp.mean(s * loss.surface_weight * surface + (1.0 - s)
                    * loss.eikonal_weight * 0.1 * eikonal)


def _targets(n, surface, seed=2):
    nrm = np.random.RandomState(seed).standard_normal((n, 3))
    nrm /= np.linalg.norm(nrm, axis=-1, keepdims=True)
    nrm[surface:] = 0.0
    return jnp.asarray(nrm, jnp.float32)


LOSS = IGRLoss(surface_weight=2.125, eikonal_weight=34816 / 18432)


# --------------------------------------------------------------------------
# the network on the normal path
# --------------------------------------------------------------------------

def test_layers_and_geometric_init():
    params = igr_init(IGRConfig(), jax.random.PRNGKey(0))
    shapes = [p["w"].shape for p in params]
    assert shapes[0] == (3, 512) and shapes[3] == (512, 509)
    assert shapes[4] == (512, 512) and shapes[-1] == (512, 1)
    n = sum(p["w"].size + p["b"].size for p in params)
    assert n == 1_839_614
    assert float(params[-1]["b"][0]) == -1.0
    assert abs(float(jnp.mean(params[-1]["w"])) - math.sqrt(math.pi / 512)) \
        < 1e-5
    # a freshly initialised IGR is near the signed distance to the unit
    # sphere: f(0) about -1
    y0 = igr_fn(IGRConfig(), params)(jnp.zeros((1, 3)))
    assert -1.5 < float(y0[0, 0]) < -0.5


@pytest.mark.parametrize("order", [0, 1, 2])
@pytest.mark.parametrize("config", [PALLAS, INTERPRETED],
                         ids=["pallas", "interpreted"])
def test_tower_matches_plain_reference(net, order, config):
    params, f = net
    x = _coords(53)                               # not a block multiple
    cg = P.compile_gradient(f, order, x, config=config)
    got = cg.apply_batched(x)
    want = _plain_tower(params, x, order)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert _scaled(g, w) <= TOL[order]


@pytest.mark.parametrize("order", [1, 2])
def test_softplus_towers_leave_nothing_to_the_interpreter(net, order):
    """Every node of the order-1 and order-2 towers, and of the order-1
    fit, runs inside a Pallas kernel: the compare, select and log1p steps
    keep a Softplus layer in its region."""
    _, f = net
    metric = REGISTRY.counter("compile_interp_nodes")
    before = metric.value(path="serve")
    cg = P.compile_gradient(f, order, _coords(64), config=PALLAS)
    assert metric.value(path="serve") - before == 0
    assert all(kernel != "interpret" for _, _, kernel in cg.dispatch)
    ops = cg.graph.counts_by_op()
    assert ops.get("Select") and ops.get("Ne") and ops.get("Log1p")


def test_fit_graph_leaves_nothing_to_the_interpreter(net):
    params, f = net
    metric = REGISTRY.counter("compile_interp_nodes")
    before = metric.value(path="fit")
    cf = compile_fit(f, LOSS, 1, _coords(64), params=params, config=PALLAS)
    assert metric.value(path="fit") - before == 0
    assert all(kind == "region" for kind, _ in _fit_units(cf.cg))


@pytest.mark.parametrize("config", [PALLAS, INTERPRETED],
                         ids=["pallas", "interpreted"])
def test_fit_value_and_grad_matches_plain_reference(net, config):
    params, f = net
    x, t = _coords(61, seed=3), _targets(61, surface=30)
    cf = compile_fit(f, LOSS, 1, x, params=params, config=config)
    loss, grads = cf.value_and_grad(params, x, t)
    want, wgrads = jax.value_and_grad(_plain_loss)(params, x, t, LOSS)
    assert abs(float(loss) - float(want)) <= TOL[1] * abs(float(want))
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(wgrads)):
        assert (float(jnp.linalg.norm(g - w))
                <= TOL_GRAD * max(float(jnp.linalg.norm(w)), 1e-30))


def test_fit_from_geometric_init_follows_plain_adam():
    """Three steps from IGR's own initialisation, whose hidden biases are
    all zero (equal leaves, bound to their own layers by the array each
    was traced from), against the plain reference under the same Adam."""
    from repro.optim.adam import AdamWConfig
    params = igr_init(CFG, jax.random.PRNGKey(4))
    x, t = _coords(96, seed=5), _targets(96, surface=48)
    cf = compile_fit(igr_fn(CFG, params), LOSS, 1, x[:16], params=params,
                     config=PALLAS)
    adam = AdamWConfig(lr=5e-3, b1=0.9, b2=0.999, eps=1e-8,
                       weight_decay=0.0, clip_norm=0.0, warmup_steps=0,
                       total_steps=10 ** 9)
    res = fit(cf, x, t, steps=3, params=params, adam=adam)
    p, m, v = params, *(jax.tree.map(jnp.zeros_like, params),) * 2
    losses = []
    for i in range(3):
        lv, g = jax.value_and_grad(_plain_loss)(p, x, t, LOSS)
        losses.append(float(lv))
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        p = jax.tree.map(
            lambda q, a, b: q - 5e-3 * (a / (1 - 0.9 ** (i + 1)))
            / (jnp.sqrt(b / (1 - 0.999 ** (i + 1))) + 1e-8), p, m, v)
    np.testing.assert_allclose(res.losses, losses, rtol=TOL[1])
    assert losses[-1] < losses[0]
    # Adam moves each element by about lr whatever its gradient's size, so
    # an element whose gradient is near round-off moves by a share of a
    # step that rounding decides: the change, per leaf, within a thousandth
    for a, b, p0 in zip(jax.tree.leaves(res.params), jax.tree.leaves(p),
                        jax.tree.leaves(params)):
        assert (float(jnp.linalg.norm(a - b))
                <= 1e-3 * float(jnp.linalg.norm(b - p0)))


# --------------------------------------------------------------------------
# the tolerances against a reference one precision step down
# --------------------------------------------------------------------------

def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


@jax.custom_vjp
def _bf16x3(a, b):
    """The TPU's three-pass bf16 product (``Precision.HIGH``), emulated:
    hi*hi + hi*lo + lo*hi of the operands' bf16 pairs."""
    (ah, al), (bh, bl) = _split(a), _split(b)
    return _dot_highest(ah, bh) + _dot_highest(ah, bl) + _dot_highest(al, bh)


_bf16x3.defvjp(lambda a, b: (_bf16x3(a, b), (a, b)),
               lambda r, ct: (_bf16x3(ct, r[1].T), _bf16x3(r[0].T, ct)))


def test_tolerances_catch_a_high_precision_dot(net):
    params, _ = net
    x = _coords(53)
    worst = {}
    for order in (0, 1, 2):
        want = _plain_tower(params, x, order)
        high = _plain_tower(params, x, order, dot=_bf16x3)
        worst[order] = max(_scaled(h, w) for h, w in zip(high, want))
    assert any(worst[o] > TOL[o] for o in worst), worst
    xs, t = _coords(61, seed=3), _targets(61, surface=30)
    g = jax.grad(_plain_loss)(params, xs, t, LOSS)
    gh = jax.grad(_plain_loss)(params, xs, t, LOSS, dot=_bf16x3)
    gaps = [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            for a, b in zip(jax.tree.leaves(gh), jax.tree.leaves(g))]
    assert max(gaps) > TOL_GRAD, gaps


# --------------------------------------------------------------------------
# each new step kind, in the kernels, against the interpreter
# --------------------------------------------------------------------------

R, C = 16, 8
SPECIAL = np.array([np.nan, np.inf, -np.inf, 0.0, -0.0, 1.0, -1.0, 2.5],
                   np.float32)


def _operands(seed, special: bool):
    rs = np.random.RandomState(seed)
    x = rs.standard_normal((R, C)).astype(np.float32)
    y = rs.standard_normal((R, C)).astype(np.float32)
    y[::3] = x[::3]                               # ties for the compares
    if special:
        x[0], y[1] = SPECIAL, SPECIAL
        x[2], y[2] = SPECIAL, SPECIAL[::-1]
    return jnp.asarray(x), jnp.asarray(y)


def _graph(op: str):
    """x, y -> the op's IR nodes, ending in a float output:
    compare: select_n(x op y, y, log1p(|x|)) with the mask in the chain;
    Log1p: log1p(exp(-|x|)) (softplus's tail);
    Eq with a scalar: select_n(x == inf, x, y) (softplus's isposinf)."""
    g = ComputeGraph()
    sh, f32 = (R, C), "float32"
    x = g.add("Input", sh, f32, params=(("idx", 0),))
    y = g.add("Input", sh, f32, params=(("idx", 1),))
    if op == "Log1p":
        a = g.add("Abs", sh, f32, (x,))
        n = g.add("Neg", sh, f32, (a,))
        e = g.add("Exp", sh, f32, (n,))
        out = g.add("Log1p", sh, f32, (e,))
        chain = [a, n, e, out]
    elif op == "EqInf":
        inf = g.add("Const", (), f32, const=np.array(np.inf, np.float32))
        m = g.add("Eq", sh, "bool", (x, inf))
        out = g.add("Select", sh, f32, (m, x, y))
        chain = [m, out]
    else:
        a = g.add("Abs", sh, f32, (x,))
        lp = g.add("Log1p", sh, f32, (a,))
        m = g.add(op, sh, "bool", (x, y))
        out = g.add("Select", sh, f32, (m, y, lp))
        chain = [m, out]
    g.outputs = [out]
    return g, chain


STEP_KINDS = ["Eq", "Ne", "Lt", "Le", "Gt", "Ge", "Log1p", "EqInf"]


def _region(g, spec):
    """A one-chain-step region spec over the chain's streamed inputs."""
    ins = []
    for i in (spec.x,) + spec.extras:
        if g.nodes[i].op != "Const" and i not in ins:
            ins.append(i)
    out = g.outputs[0]
    return RegionKernelSpec(steps=(("chain", out, spec.x, spec.steps,
                                    spec.extras),),
                            stream_inputs=tuple(ins), residents=(),
                            outputs=(out,)), ins


def _interpreted(g, env):
    """Every node's value through the interpreter (the chain's operands
    among them)."""
    for nid in g.topo_order():
        if nid not in env:
            n = g.nodes[nid]
            env[nid] = (jnp.asarray(n.const) if n.op == "Const" else
                        _eval_node(n, [env[i] for i in n.inputs]))
    return env


@pytest.mark.parametrize("op", STEP_KINDS)
def test_step_kind_forward_in_kernels(op):
    g, chain = _graph(op)
    x, y = _operands(7, special=True)
    env = _interpreted(g, {0: x, 1: y})
    want = reference_executor(g)(x, y)[0]
    spec = build_chain_spec(g, chain, resident={n for n in g.nodes
                                                if g.nodes[n].op == "Const"})
    assert spec is not None, f"{op} is not a chain step"
    extras = tuple(env[e] for e in spec.extras)
    got = fused_chain(env[spec.x], spec.steps, extras, block_rows=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    rspec, ins = _region(g, spec)
    outs = region_call(rspec, [env[i] for i in ins], [], [],
                       ((C, "float32"),), bm=8)
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(want))


@pytest.mark.parametrize("op", STEP_KINDS)
def test_step_kind_backward_in_region_kernel(op):
    g, chain = _graph(op)
    x, y = _operands(8, special=False)
    env = _interpreted(g, {0: x, 1: y})
    spec = build_chain_spec(g, chain, resident={n for n in g.nodes
                                                if g.nodes[n].op == "Const"})
    rspec, ins = _region(g, spec)
    ct = jnp.asarray(np.random.RandomState(9).standard_normal((R, C)),
                     jnp.float32)
    call = region_grad_fn(rspec, ((C, "float32"),), 8)
    _, pull = jax.vjp(lambda *a: call(*a)[0], *[env[i] for i in ins])
    got = pull(ct)

    def interpreted(*vals):
        local = dict(env)
        local.update(zip(ins, vals))
        for nid in chain:
            n = g.nodes[nid]
            local[nid] = _eval_node(n, [local[i] for i in n.inputs])
        return local[g.outputs[0]]
    _, pull_ref = jax.vjp(interpreted, *[env[i] for i in ins])
    want = pull_ref(ct)
    for a, b in zip(got, want):
        assert _scaled(a, b) <= TOL_STEP, (op, _scaled(a, b))


def test_ne_keeps_nan_and_compares_swap_sides():
    """``ne`` is the unordered compare (true on NaN, as XLA's); a compare
    whose chain value is its right operand swaps to the left."""
    g = ComputeGraph()
    x = g.add("Input", (R, C), "float32", params=(("idx", 0),))
    y = g.add("Input", (R, C), "float32", params=(("idx", 1),))
    a = g.add("Abs", (R, C), "float32", (x,))
    m = g.add("Lt", (R, C), "bool", (y, a))       # y < |x|: chain on the right
    g.outputs = [m]
    spec = build_chain_spec(g, [a, m], resident=set())
    assert spec.steps == (("abs", None), ("gt", None))
    xv, yv = _operands(10, special=True)
    got = fused_chain(xv, spec.steps, (yv,), out_dtype=jnp.bool_,
                      block_rows=8)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(yv < jnp.abs(xv)))
    nan = jnp.full((8, 8), jnp.nan)
    isnan = fused_chain(nan, (("ne", None),), (nan,), out_dtype=jnp.bool_)
    assert bool(jnp.all(isnan))


@pytest.mark.parametrize("order", [1, 2])
def test_emitted_source_matches_the_artifact(net, order):
    """The emitted module (one function per region, the new ops as jnp
    expressions) computes what the artifact serves."""
    from repro.core import codegen
    _, f = net
    x = _coords(64)
    cg = P.compile_gradient(f, order, x, config=PALLAS)
    pipe, _ = codegen.load_generated(cg.source)
    got = pipe(codegen.graph_consts(cg.graph, cg.plan), x)
    for a, b in zip(got, cg.apply(x)):
        assert _scaled(a, b) <= TOL[order]
