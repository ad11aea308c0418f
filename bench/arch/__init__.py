"""Architecture plug-ins, chosen by a configuration file's ``model_type``.

What the harness knows of one architecture lives in two files of its
own, so that a configuration of a new architecture joins the benchmark
as new files alone:

``bench/arch/<model_type>.py`` -- how the program is built from the file
and what the yardstick counts of it:

* ``arch_config(cfg, compute_dtype)``: the program's ``ArchConfig``;
* ``stored_dtype(weights_dtype)``: ``name -> dtype`` of each stored leaf;
* ``decode_token(cfg, attended)``, ``prefill(cfg, n)``,
  ``train_token(cfg, seq)``: FLOPs, as ``bench/flops.py`` defines them;
* ``attention(cfg)``: what one decode step's paged attention reads, a
  dict of ``layers`` (indices of the layers that attend), ``heads``,
  ``kv_heads`` and ``head_dim``;
* ``tiny(cfg)``: the file cut to the rehearsal's size
  (``bench/rehearse.py``);
* optionally ``INITIALIZERS``, ``{leaf kind: init(key, shape)}`` for leaf
  kinds of its own (``bench/weights.py`` draws ``w``, ``b``, ``scale``
  and ``table``), and ``STACKED``, the name prefixes of leaves stacked
  over layers (default ``bench.weights.STACKED``, ``("layers/",)``).

``bench/reference/<model_type>.py`` -- the plain float32 reference:
``leaf_shapes``, ``make_weights``, ``scored_logits`` and ``nll_sum``.
"""
from __future__ import annotations

import importlib
import types
from typing import NamedTuple

import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


class Plugin(NamedTuple):
    module: types.ModuleType  # bench/arch/<model_type>.py
    reference: types.ModuleType  # bench/reference/<model_type>.py


def _import(package: str, model_type: str) -> types.ModuleType:
    name = f"bench.{package}.{model_type}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ModuleNotFoundError(
            f"no {package} module for model_type {model_type!r}: "
            f"add bench/{package}/{model_type}.py", name=name) from None


def load(cfg: dict) -> Plugin:
    """The architecture and reference modules of ``cfg['model_type']``."""
    model_type = cfg.get("model_type")
    if not isinstance(model_type, str) or not model_type.isidentifier():
        raise ValueError(f"model_type {model_type!r} cannot name a module under bench/arch/")
    return Plugin(_import("arch", model_type), _import("reference", model_type))
