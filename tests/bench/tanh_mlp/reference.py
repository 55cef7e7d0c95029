"""Plain float32 reference for the rehearsal's tanh MLP

    h_0 = tanh(x W_0 + b_0),  h_k = tanh(h W_k + b_k),  y = h W_L + b_L

with ``hidden_layers`` tanh layers of ``hidden_features``, in straight
``jax.numpy``.  It keeps the contract of a reference module: the weights
(``init_params``, ``param_bytes``), the network (``apply``), the served
tower (``tower``), the fit's loss (``grad_mse``) and optimizer step
(``adam_step``), each dot at the ``precision`` it is given.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_sizes(cfg: dict) -> list[int]:
    return ([cfg["in_features"]]
            + [cfg["hidden_features"]] * cfg["hidden_layers"]
            + [cfg["out_features"]])


def init_params(cfg: dict, key) -> list[dict]:
    """Glorot-uniform weights; biases uniform within 1/sqrt(fan-in)."""
    sizes = layer_sizes(cfg)
    params = []
    for k, fin, fout in zip(jax.random.split(key, len(sizes) - 1),
                            sizes[:-1], sizes[1:]):
        kw, kb = jax.random.split(k)
        bw, bb = math.sqrt(6.0 / (fin + fout)), 1.0 / math.sqrt(fin)
        params.append({
            "w": jax.random.uniform(kw, (fin, fout), jnp.float32, -bw, bw),
            "b": jax.random.uniform(kb, (fout,), jnp.float32, -bb, bb)})
    return params


def param_bytes(cfg: dict) -> int:
    sizes = layer_sizes(cfg)
    return 4 * sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))


def apply(cfg: dict, params, x, precision: str = "highest", dot=None):
    p = getattr(jax.lax.Precision, precision.upper())
    dot = dot or (lambda a, b: jnp.dot(a, b, precision=p))
    h = x
    for i, layer in enumerate(params):
        h = dot(h, layer["w"]) + layer["b"]
        if i < len(params) - 1:
            h = jnp.tanh(h)
    return h


def tower(cfg: dict, params, x, order: int, precision: str = "highest",
          dot=None):
    """``y``, then every order's input derivatives in the served layout,
    one repeated reverse-mode derivative per (channel, index path)."""
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


def grad_mse(cfg: dict, params, x, target, precision: str = "highest",
             dot=None):
    """Mean over rows of |dy/dx - target|^2, target [N, C * D]."""
    dy = jnp.concatenate(tower(cfg, params, x, 1, precision, dot)[1:], -1)
    return jnp.mean(jnp.sum((dy - target) ** 2, -1))


def adam_step(hp: dict, params, grads, state, step: int):
    """One Adam step, state ``(m, v)`` per leaf, constant rate."""
    b1, b2, lr, eps = hp["b1"], hp["b2"], hp["lr"], hp["eps"]
    t = step + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state[0], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state[1],
                     grads)
    new = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / (1 - b1 ** t))
        / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps), params, m, v)
    return new, (m, v)
