"""Plain float32 reference for the IGR configuration.

IGR (Gropp et al., Implicit Geometric Regularization for Learning Shapes,
ICML 2020, arXiv:2002.10099; ``code/model/network.py``, ``ImplicitNet``):
linear layers of the widths ``dims``, each followed by Softplus(beta) =
softplus(beta z) / beta, and a linear output layer; before each layer in
``skip_in`` the input is concatenated back in and the result divided by
sqrt(2), and the layer before it outputs ``dims - in_features`` columns.
Everything here is straight ``jax.numpy``: no kernels, no blocking, no
batching beyond whole arrays.  It imports nothing of the program under
test; the benchmark makes the weights here and hands the same pytree to
both.

``precision`` names the matmul precision of every dot, and autodiff keeps
it on every dot it derives: ``"highest"`` is the reference, ``"high"``
(three bf16 passes on a TPU) is the correctness control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_sizes(cfg: dict) -> list[tuple[int, int]]:
    """``(fan_in, fan_out)`` per linear layer: ``ImplicitNet``'s layout."""
    d_in = cfg["in_features"]
    dims = [d_in] + list(cfg["dims"]) + [cfg["out_features"]]
    return [(dims[i], dims[i + 1] - (d_in if i + 1 in cfg["skip_in"] else 0))
            for i in range(len(dims) - 1)]


def init_params(cfg: dict, key) -> list[dict]:
    """IGR's geometric initialisation: the last layer's weight
    N(sqrt(pi) / sqrt(fan_in), 1e-5) and bias -radius_init, every other
    weight N(0, sqrt(2) / sqrt(fan_out)) and bias 0."""
    if not cfg["geometric_init"]:
        raise ValueError("the configuration states IGR's geometric init")
    sizes = layer_sizes(cfg)
    keys = jax.random.split(key, len(sizes))
    params = []
    for i, (fin, fout) in enumerate(sizes):
        z = jax.random.normal(keys[i], (fin, fout), jnp.float32)
        if i == len(sizes) - 1:
            params.append({"w": math.sqrt(math.pi) / math.sqrt(fin) + 1e-5 * z,
                           "b": jnp.full((fout,), -cfg["radius_init"],
                                         jnp.float32)})
        else:
            params.append({"w": math.sqrt(2.0) / math.sqrt(fout) * z,
                           "b": jnp.zeros((fout,), jnp.float32)})
    return params


def param_bytes(cfg: dict) -> int:
    return 4 * sum(a * b + b for a, b in layer_sizes(cfg))


def _dot(precision: str):
    p = getattr(jax.lax.Precision, precision.upper())
    return lambda a, b: jnp.dot(a, b, precision=p)


def apply(cfg: dict, params, x, precision: str = "highest", dot=None):
    """x [..., in] -> y [..., out]."""
    dot = dot or _dot(precision)
    beta = cfg["beta"]
    h = x
    for i, layer in enumerate(params):
        if i in cfg["skip_in"]:
            h = jnp.concatenate([h, x], -1) / math.sqrt(2.0)
        h = dot(h, layer["w"]) + layer["b"]
        if i < len(params) - 1:
            h = jnp.logaddexp(beta * h, 0.0) / beta
    return h


def tower(cfg: dict, params, x, order: int, precision: str = "highest",
          dot=None):
    """The n-th order input-gradient outputs in the served layout: ``y``
    [B, C]; then per channel c, dy_c/dx [B, D]; and so on, one repeated
    reverse-mode derivative per (channel, index path) with the batch-sum
    trick."""
    C, D = cfg["out_features"], cfg["in_features"]

    def f(z):
        return apply(cfg, params, z, precision, dot)

    outs = [f(x)]
    level = [(lambda z, c=c: f(z)[:, c].sum()) for c in range(C)]
    for _ in range(order):
        grads = [jax.grad(s) for s in level]
        outs.extend(g(x) for g in grads)
        level = [(lambda z, g=g, i=i: g(z)[:, i].sum())
                 for g in grads for i in range(D)]
    return tuple(outs)


def loss_weights(cfg: dict) -> tuple[float, float]:
    """IGR's step holds ``points_batch`` surface points and, for the
    eikonal term, as many perturbed copies plus ``global_samples`` global
    points; the weights make a step's mean row loss the sum of IGR's
    per-term means: (rows / surface rows, rows / eikonal rows)."""
    surface = cfg["points_batch"]
    eikonal = surface + cfg["global_samples"]
    rows = surface + eikonal
    return rows / surface, rows / eikonal


def igr_loss(cfg: dict, params, x, target, precision: str = "highest",
             dot=None):
    """Mean over rows of IGR's loss, one channel: target [N, D] holds a
    surface row's unit normal n, or zeros on an eikonal-only row;
    s = |n|^2 picks the terms.  Surface rows: |f| + normals_lambda
    |grad f - n|; eikonal rows: grad_lambda (|grad f| - 1)^2."""
    a, e = loss_weights(cfg)
    y, dy = tower(cfg, params, x, 1, precision, dot)
    s = jnp.sum(target ** 2, -1)
    surface = (jnp.abs(y[:, 0]) + cfg["normals_lambda"]
               * jnp.linalg.norm(dy - target, axis=-1))
    eikonal = (jnp.linalg.norm(dy, axis=-1) - 1.0) ** 2
    return jnp.mean(s * a * surface
                    + (1.0 - s) * e * cfg["grad_lambda"] * eikonal)


def adam_step(hp: dict, params, grads, state, step: int):
    """One Adam step (no weight decay, no clipping, constant rate), state
    ``(m, v)`` per leaf: the update the fit traffic asks of the program."""
    b1, b2, lr, eps = hp["b1"], hp["b2"], hp["lr"], hp["eps"]
    t = step + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state[0], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state[1],
                     grads)
    new = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / (1 - b1 ** t))
        / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps), params, m, v)
    return new, (m, v)
