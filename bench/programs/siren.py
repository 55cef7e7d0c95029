"""SIREN as the program traces it, through its public ``siren_fn``."""

from __future__ import annotations


def siren_config(cfg: dict):
    """The program's SirenConfig for a configuration file.  The program
    counts hidden activations (``hidden_layers``); SIREN's ``FCBlock``
    counts hidden-to-hidden layers (``num_hidden_layers``), one fewer."""
    from repro.configs.siren import SirenConfig
    if cfg["first_omega_0"] != cfg["hidden_omega_0"]:
        raise ValueError("the program takes one w0 for every layer")
    return SirenConfig(in_features=cfg["in_features"],
                       out_features=cfg["out_features"],
                       hidden_features=cfg["hidden_features"],
                       hidden_layers=cfg["num_hidden_layers"] + 1,
                       w0=float(cfg["hidden_omega_0"]))


def inr(cfg: dict, params):
    """The INR as the program traces it: ``x [B, in] -> y [B, out]``."""
    from repro.inr.siren import siren_fn
    return siren_fn(siren_config(cfg), params)
