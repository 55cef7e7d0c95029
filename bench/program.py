"""The program under test, as the benchmark drives it.

Only the system's public entry points are used: the SIREN function, the
compile front doors (``compile_gradient``, ``filter_bank``,
``compile_fit``), the engines and ``fit``.  Weights and inputs are the
benchmark's own, made from the seed; the program is handed them.
"""

from __future__ import annotations


def siren_config(cfg: dict):
    """The program's SirenConfig for a configuration file.  The program
    counts hidden activations (``hidden_layers``); SIREN's ``FCBlock``
    counts hidden-to-hidden layers (``num_hidden_layers``), one fewer."""
    from repro.configs.siren import SirenConfig
    if cfg["first_omega_0"] != cfg["hidden_omega_0"]:
        raise ValueError("the program takes one w0 for every layer")
    if cfg["precision"] != "highest" or cfg["dtype"] != "float32":
        raise ValueError("the program computes float32 at highest precision")
    return SirenConfig(in_features=cfg["in_features"],
                       out_features=cfg["out_features"],
                       hidden_features=cfg["hidden_features"],
                       hidden_layers=cfg["num_hidden_layers"] + 1,
                       w0=float(cfg["hidden_omega_0"]))


def siren(cfg: dict, params):
    """The INR as the program traces it: ``x [B, in] -> y [B, out]``."""
    from repro.inr.siren import siren_fn
    return siren_fn(siren_config(cfg), params)


def release() -> None:
    """Drop the program's in-process compile caches, so that what the
    window compiled is freed before the reference runs."""
    import gc
    from repro.core import pipeline
    pipeline.clear_compile_cache()
    gc.collect()
