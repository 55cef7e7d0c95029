"""Fit objectives — order-n supervision targets over the streamed outputs.

An objective maps ONE block of pipeline outputs (the same tuple
``CompiledGradient`` streams for serving: ``y``, then the order-1 gradients
per channel, then the order-2 rows per (channel, input), ... — the
``paper_gradients`` layout) plus a target block to a per-row loss vector.
The fit compiler masks and sums those rows across blocks, so an objective
never sees padding and never reduces across the grid itself.

Objectives are frozen dataclasses: hashable, so they key the compile-fit
cache next to the traced function and config, and fingerprintable for the
ArtifactStore's request log.

``min_order`` declares the smallest gradient order whose streamed outputs
the objective reads — ``compile_fit`` validates the requested order covers
it (a Laplacian loss through an order-1 artifact has no second-derivative
columns to read).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp


@dataclass(frozen=True)
class Objective:
    """Base: ``row_loss(outs, target, C, D)`` returns ``[rows]`` losses for
    one block; ``C``/``D`` are the INR's out/in features (fixes where each
    derivative lives in the streamed output tuple)."""
    min_order: int = 0

    def row_loss(self, outs, target, C: int, D: int):
        raise NotImplementedError

    def target_cols(self, C: int, D: int) -> int:
        """Trailing width of one target row (targets arrive ``[N, cols]``)."""
        raise NotImplementedError


@dataclass(frozen=True)
class ValueMSE(Objective):
    """Plain reconstruction: ``|y - t|^2`` summed over channels."""
    min_order: int = 0

    def row_loss(self, outs, target, C: int, D: int):
        return jnp.sum((outs[0] - target) ** 2, axis=-1)

    def target_cols(self, C: int, D: int) -> int:
        return C


@dataclass(frozen=True)
class GradMSE(Objective):
    """First-order (Sobel-style) supervision: match the full Jacobian rows.
    Target layout is ``[N, C*D]`` — channel-major, the ``feature_vector``
    column order."""
    min_order: int = 1

    def row_loss(self, outs, target, C: int, D: int):
        dy = jnp.concatenate([outs[1 + c] for c in range(C)], axis=-1)
        return jnp.sum((dy - target) ** 2, axis=-1)

    def target_cols(self, C: int, D: int) -> int:
        return C * D


@dataclass(frozen=True)
class LaplacianMSE(Objective):
    """Second-order supervision: match the Laplacian trace
    ``sum_i d2y_c/dx_i^2`` per channel (the edge/heat-flow objective of the
    INR-editing workflows).  Target layout is ``[N, C]``."""
    min_order: int = 2

    def row_loss(self, outs, target, C: int, D: int):
        base = 1 + C                       # order-2 rows start after y + dy
        lap = []
        for c in range(C):
            rows = [outs[base + c * D + i][:, i] for i in range(D)]
            lap.append(sum(rows))
        lap = jnp.stack(lap, axis=-1)
        return jnp.sum((lap - target) ** 2, axis=-1)

    def target_cols(self, C: int, D: int) -> int:
        return C


@dataclass(frozen=True)
class IGRLoss(Objective):
    """IGR's surface reconstruction loss (Gropp et al., ICML 2020), per
    row, over one channel, with IGR's published weights (normals 1,
    eikonal 0.1).  Target layout is ``[N, D]``: the unit normal ``n`` of a
    surface row, the zero vector on an eikonal-only row, so ``s = |n|^2``
    marks the surface rows:

        s a (|y| + |dy/dx - n|) + (1 - s) e 0.1 (|dy/dx| - 1)^2.

    ``surface_weight`` (a) and ``eikonal_weight`` (e) are the step's rows
    over the surface and eikonal rows a step holds, so that the step's
    mean row loss is IGR's sum of per-term means."""
    min_order: int = 1
    surface_weight: float = 1.0
    eikonal_weight: float = 1.0

    def row_loss(self, outs, target, C: int, D: int):
        if C != 1:
            raise ValueError("IGRLoss is written for one output channel")
        y, dy = outs[0][:, 0], outs[1]
        s = jnp.sum(target * target, axis=-1)
        surface = (jnp.abs(y)
                   + jnp.sqrt(jnp.sum((dy - target) ** 2, axis=-1)))
        eikonal = (jnp.sqrt(jnp.sum(dy * dy, axis=-1)) - 1.0) ** 2
        return (s * self.surface_weight * surface
                + (1.0 - s) * self.eikonal_weight * 0.1 * eikonal)

    def target_cols(self, C: int, D: int) -> int:
        return C * D
