"""Seeded weights, made on the device, leaf by name.

A leaf is named by its path in the parameter tree (``layers/attn/wq/w``)
and drawn from a key folded from the seed and the name, so the program's
copy (one jitted call over the whole tree) and the reference's copy
(leaf by leaf, layer by layer if it likes) hold the same numbers without
either reading the other's. Leaves whose names start with one of the
architecture's ``STACKED`` prefixes (``layers/`` unless it says
otherwise) are stacked over layers; layer ``l`` of such a leaf is drawn
from ``fold_in(key, l)``.

Scales: matrices N(0, 1/fan_in); biases N(0, 0.1^2); norm scales
1 + N(0, 0.1^2); embedding tables N(0, 1/d_model), which puts the tied
unembedding's logits at about unit spread. A leaf kind of the
architecture's own is drawn by its ``INITIALIZERS`` entry
(``bench/arch/__init__.py``).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

STACKED = ("layers/",)  # stacked-leaf prefixes of an architecture that names none


def base_key(seed: int) -> jax.Array:
    s = int(seed)
    k = jax.random.fold_in(jax.random.PRNGKey(0), np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((s >> 32) & 0xFFFFFFFF))


def leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())))


def _draw(key, name: str, shape, arch) -> jax.Array:
    kind = name.rsplit("/", 1)[-1]
    own = getattr(arch, "INITIALIZERS", {}).get(kind)
    if own is not None:
        return own(key, shape)
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "w":
        return z / np.sqrt(shape[-2])
    if kind in ("b", "bias"):
        return 0.1 * z
    if kind == "scale":
        return 1.0 + 0.1 * z
    if kind == "table":
        return z / np.sqrt(shape[-1])
    raise ValueError(f"no initializer for leaf {name!r}")


def leaf(key: jax.Array, name: str, shape, dtype, layer=None, arch=None) -> jax.Array:
    """One leaf (or, with ``layer``, one layer of a stacked leaf) from
    the seed's ``base_key``. ``arch`` is the architecture module
    (``bench/arch/<model_type>.py``), for its own leaf kinds and stacked
    prefixes. Traced keys and layers are fine, so one compiled program
    serves every seed."""
    key = leaf_key(key, name)
    if not name.startswith(tuple(getattr(arch, "STACKED", STACKED))):
        return _draw(key, name, tuple(shape), arch).astype(dtype)
    if layer is not None:
        return _draw(jax.random.fold_in(key, layer), name, tuple(shape[1:]), arch).astype(dtype)
    layers = jnp.arange(shape[0])
    return jax.vmap(lambda l: _draw(jax.random.fold_in(key, l), name,
                                    tuple(shape[1:]), arch))(layers).astype(dtype)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def make_params(tree_like, seed: int, dtype_of, out_shardings=None, arch=None):
    """The whole tree in one jitted call on the device. ``dtype_of(name)``
    gives each leaf's stored dtype; ``arch`` as for ``leaf``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree_like)
    names = [path_name(p) for p, _ in flat]
    shapes = [x.shape for _, x in flat]

    def build(key):
        return jax.tree_util.tree_unflatten(
            treedef, [leaf(key, n, s, dtype_of(n), arch=arch) for n, s in zip(names, shapes)])

    return jax.jit(build, out_shardings=out_shardings)(base_key(seed))
