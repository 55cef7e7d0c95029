"""Streamed INR fitting (DESIGN.md §11): ``compile_fit`` builds a cached
``CompiledFit`` over the serving block pipeline; ``fit`` / ``fit_many``
drive it through AdamW and stream converged weights into the store."""

from repro.fit.compile import CompiledFit, compile_fit
from repro.fit.engine import FitResult, fit, fit_many
from repro.fit.objectives import (GradMSE, IGRLoss, LaplacianMSE, Objective,
                                  ValueMSE)

__all__ = ["CompiledFit", "compile_fit", "FitResult", "fit", "fit_many",
           "Objective", "ValueMSE", "GradMSE", "LaplacianMSE", "IGRLoss"]
