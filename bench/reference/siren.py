"""Plain float32 reference for the SIREN configurations.

SIREN (Sitzmann et al., NeurIPS 2020, arXiv:2006.09661): a sine MLP

    h_0 = sin(w0 (x W_0 + b_0)),  h_k = sin(w0 (h W_k + b_k)),  y = h W_L + b_L

with the paper's initialisation.  Everything here is straight
``jax.numpy``: no kernels, no blocking, no batching beyond whole arrays.
It imports nothing of the program under test and takes nothing it made;
the benchmark makes the weights here and hands the same pytree to both.

``precision`` names the matmul precision of every dot, and autodiff keeps
it on every dot it derives: ``"highest"`` is the reference,
``"high"`` (three bf16 passes on a TPU) is the correctness control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

def layer_sizes(cfg: dict) -> list[int]:
    """in -> hidden (first layer), ``num_hidden_layers`` hidden -> hidden
    layers, hidden -> out: the SIREN repository's ``FCBlock`` layout."""
    h = cfg["hidden_features"]
    return ([cfg["in_features"]] + [h] * (cfg["num_hidden_layers"] + 1)
            + [cfg["out_features"]])


def init_params(cfg: dict, key) -> list[dict]:
    """SIREN's initialisation: first layer U(-1/in, 1/in), later layers
    U(-sqrt(6/in)/w0, +sqrt(6/in)/w0); biases drawn with the same bound."""
    sizes = layer_sizes(cfg)
    keys = jax.random.split(key, len(sizes) - 1)
    params = []
    for i, (fin, fout) in enumerate(zip(sizes[:-1], sizes[1:])):
        k1, k2 = jax.random.split(keys[i])
        bound = (1.0 / fin if i == 0
                 else math.sqrt(6.0 / fin) / cfg["hidden_omega_0"])
        params.append({
            "w": jax.random.uniform(k1, (fin, fout), jnp.float32,
                                    -bound, bound),
            "b": jax.random.uniform(k2, (fout,), jnp.float32, -bound, bound)})
    return params


def param_bytes(cfg: dict) -> int:
    sizes = layer_sizes(cfg)
    return 4 * sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))


def _dot(precision: str):
    p = getattr(jax.lax.Precision, precision.upper())
    return lambda a, b: jnp.dot(a, b, precision=p)


def apply(cfg: dict, params, x, precision: str = "highest", dot=None):
    """x [..., in] -> y [..., out].  ``dot`` replaces the matmul (tests
    use it to stand in for a precision the CPU does not implement)."""
    dot = dot or _dot(precision)
    w0 = cfg["first_omega_0"]
    h = x
    for i, layer in enumerate(params):
        h = dot(h, layer["w"]) + layer["b"]
        if i < len(params) - 1:
            h = jnp.sin(w0 * h)
            w0 = cfg["hidden_omega_0"]
    return h


def tower(cfg: dict, params, x, order: int, precision: str = "highest",
          dot=None):
    """The n-th order input-gradient outputs in the served layout: ``y``
    [B, C]; then per channel c, dy_c/dx [B, D]; then per (c, i),
    d(dy_c/dx_i)/dx [B, D]; and so on, one repeated reverse-mode
    derivative per (channel, index path) with the batch-sum trick."""
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


def filter_head(name: str, outs, cfg: dict, alpha: float):
    """The closed-form image filters over an order-2 tower (channel 0):
    identity, gradient magnitude ``edge``, Hessian trace ``laplacian``, and
    one heat-flow step ``blur`` = y + alpha lap, ``sharpen`` = y - alpha
    lap."""
    C, D = cfg["out_features"], cfg["in_features"]
    if C != 1:
        raise ValueError("the filter heads are written for one channel")
    y = outs[0]
    if name == "identity":
        return y
    if name == "edge":
        return jnp.sqrt(jnp.sum(outs[1] ** 2, -1, keepdims=True))
    lap = sum(outs[2 + i][:, i:i + 1] for i in range(D))
    if name == "laplacian":
        return lap
    if name == "blur":
        return y + alpha * lap
    if name == "sharpen":
        return y - alpha * lap
    raise KeyError(f"unknown filter {name!r}")


def grad_mse(cfg: dict, params, x, target, precision: str = "highest",
             dot=None):
    """Mean over rows of |dy/dx - target|^2 (SIREN's normal supervision,
    one channel): target is [N, C * D], channel-major."""
    outs = tower(cfg, params, x, 1, precision, dot)
    dy = jnp.concatenate(outs[1:], -1)
    return jnp.mean(jnp.sum((dy - target) ** 2, -1))


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
