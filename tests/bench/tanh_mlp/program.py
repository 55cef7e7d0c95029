"""The rehearsal's tanh MLP as the program traces it: a plain JAX
function, which the compile front doors take as they take SIREN's."""

from __future__ import annotations


def inr(cfg: dict, params):
    import jax
    import jax.numpy as jnp

    def f(x):
        h = x
        for i, layer in enumerate(params):
            h = jnp.dot(h, layer["w"],
                        precision=jax.lax.Precision.HIGHEST) + layer["b"]
            if i < len(params) - 1:
                h = jnp.tanh(h)
        return h
    return f
