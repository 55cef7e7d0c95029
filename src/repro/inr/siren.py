"""SIREN (Sitzmann et al. [3]) — the INR architecture evaluated by the paper.

f: R^in -> R^out, MLP with sine activations:
    h_0 = sin(w0 (W_0 x + b_0));  h_k = sin(w0 (W_k h + b_k));  y = W_L h + b_L
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.siren import SirenConfig


def siren_init(cfg: SirenConfig, key) -> list[dict]:
    sizes = ([cfg.in_features] + [cfg.hidden_features] * cfg.hidden_layers
             + [cfg.out_features])
    params = []
    keys = jax.random.split(key, len(sizes) - 1)
    for i, (fin, fout) in enumerate(zip(sizes[:-1], sizes[1:])):
        k1, k2 = jax.random.split(keys[i])
        if i == 0:
            bound = 1.0 / fin
        else:
            bound = math.sqrt(6.0 / fin) / cfg.w0
        w = jax.random.uniform(k1, (fin, fout), jnp.float32, -bound, bound)
        b = jax.random.uniform(k2, (fout,), jnp.float32, -bound, bound)
        params.append({"w": w, "b": b})
    return params


def siren_apply(params: list[dict], x: jnp.ndarray, w0: float = 30.0):
    """x: [..., in] -> [..., out]."""
    h = x
    for i, layer in enumerate(params):
        h = jnp.dot(h, layer["w"],
                    precision=jax.lax.Precision.HIGHEST) + layer["b"]
        if i < len(params) - 1:
            h = jnp.sin(w0 * h)
    return h


def siren_fn(cfg: SirenConfig, params):
    def f(x):
        return siren_apply(params, x, cfg.w0)
    return f
