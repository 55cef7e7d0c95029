"""IGR's ``ImplicitNet`` (Gropp et al., Implicit Geometric Regularization
for Learning Shapes, ICML 2020, arXiv:2002.10099) — the Softplus SDF
backbone that SAL, IDR, NeuS and VolSDF share.

f: R^in -> R^out, an MLP of ``hidden_layers`` linear layers of width
``hidden_features``, each followed by Softplus(beta), and a linear output
layer.  Before each layer in ``skip_in`` the input is concatenated back in
and the result divided by sqrt(2); the layer before a skip outputs
``hidden_features - in_features`` columns, so the concatenation is
``hidden_features`` wide again:

    h_0 = sp(x W_0 + b_0);  h_k = sp([h_{k-1}, x] / sqrt(2) W_k + b_k) at a
    skip, else sp(h_{k-1} W_k + b_k);  y = h W_L + b_L,
    sp(z) = softplus(beta z) / beta.

``torch.nn.Softplus`` turns linear above ``beta z = 20``; here the
softplus is exact there too.  The two differ by under 1e-10 at beta = 100.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class IGRConfig:
    """IGR's published reconstruction network (``setup.conf``)."""
    in_features: int = 3
    out_features: int = 1
    hidden_features: int = 512
    hidden_layers: int = 8
    skip_in: tuple[int, ...] = (4,)
    beta: float = 100.0
    radius_init: float = 1.0


def layer_sizes(cfg: IGRConfig) -> list[tuple[int, int]]:
    """``(fan_in, fan_out)`` of each linear layer, output layer last."""
    dims = ([cfg.in_features] + [cfg.hidden_features] * cfg.hidden_layers
            + [cfg.out_features])
    return [(dims[i], dims[i + 1] - (cfg.in_features
                                     if i + 1 in cfg.skip_in else 0))
            for i in range(len(dims) - 1)]


def igr_init(cfg: IGRConfig, key) -> list[dict]:
    """IGR's geometric initialisation, so that f starts near the signed
    distance to a sphere of radius ``radius_init``: the output layer's
    weights N(sqrt(pi) / sqrt(fan_in), 1e-5) and bias ``-radius_init``;
    every other layer N(0, sqrt(2) / sqrt(fan_out)) and bias 0."""
    sizes = layer_sizes(cfg)
    keys = jax.random.split(key, len(sizes))
    params = []
    for i, (fin, fout) in enumerate(sizes):
        z = jax.random.normal(keys[i], (fin, fout), jnp.float32)
        if i == len(sizes) - 1:
            w = math.sqrt(math.pi) / math.sqrt(fin) + 1e-5 * z
            b = jnp.full((fout,), -cfg.radius_init, jnp.float32)
        else:
            w = math.sqrt(2.0) / math.sqrt(fout) * z
            b = jnp.zeros((fout,), jnp.float32)
        params.append({"w": w, "b": b})
    return params


def igr_apply(params: list[dict], x: jnp.ndarray, cfg: IGRConfig):
    """x: [..., in] -> [..., out]."""
    h = x
    for i, layer in enumerate(params):
        if i in cfg.skip_in:
            h = jnp.concatenate([h, x], -1) / math.sqrt(2.0)
        h = jnp.dot(h, layer["w"],
                    precision=jax.lax.Precision.HIGHEST) + layer["b"]
        if i < len(params) - 1:
            h = jax.nn.softplus(cfg.beta * h) / cfg.beta
    return h


def igr_fn(cfg: IGRConfig, params):
    def f(x):
        return igr_apply(params, x, cfg)
    return f
