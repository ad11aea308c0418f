"""How the benchmark builds the system under test from a configuration
file: the architecture module that the file's ``model_type`` names
(``bench/arch``) maps its keys onto the program's own ``ArchConfig`` and
says which leaves are stored in which dtype."""
from __future__ import annotations

from bench import arch


def arch_config(cfg: dict, compute_dtype: str):
    """The program's ArchConfig for the config file ``cfg``."""
    return arch.load(cfg).module.arch_config(cfg, compute_dtype)


def stored_dtype(cfg: dict):
    """``name -> dtype`` of each stored leaf, from the file's
    ``weights_dtype``."""
    return arch.load(cfg).module.stored_dtype(cfg["weights_dtype"])
