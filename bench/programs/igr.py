"""IGR's ``ImplicitNet`` as the program traces it, through its public
``igr_fn``."""

from __future__ import annotations


def igr_config(cfg: dict):
    """The program's IGRConfig for a configuration file (IGR's
    ``setup.conf`` names: one hidden width ``dims``, ``skip_in``,
    ``beta``, ``radius_init``)."""
    from repro.inr.igr import IGRConfig
    if len(set(cfg["dims"])) != 1:
        raise ValueError("the program takes one hidden width")
    if not cfg["geometric_init"]:
        raise ValueError("the program initialises as IGR does (geometric)")
    return IGRConfig(in_features=cfg["in_features"],
                     out_features=cfg["out_features"],
                     hidden_features=cfg["dims"][0],
                     hidden_layers=len(cfg["dims"]),
                     skip_in=tuple(cfg["skip_in"]), beta=float(cfg["beta"]),
                     radius_init=float(cfg["radius_init"]))


def inr(cfg: dict, params):
    """The INR as the program traces it: ``x [B, in] -> y [B, out]``."""
    from repro.inr.igr import igr_fn
    return igr_fn(igr_config(cfg), params)
