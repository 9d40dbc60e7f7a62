"""Weights and token batches made from ``--seed`` by the benchmark itself.

Every leaf is named by its path (``stack/scan/0/attn/w_q``) and drawn from a
key folded from the seed and that name, so the program's tree and the plain
reference regenerate identical values without sharing any object.  A leaf
stacked over layers (``stack/scan/...``, leading axis = layer) draws each layer
from its own key, so the reference can rebuild one layer at a time.
"""

from __future__ import annotations

import math
import zlib

import numpy as np

import jax
import jax.numpy as jnp


def seed_words(seed: int) -> np.ndarray:
    """A seed of up to 63 bits as two uint32 words (traced, so one compiled
    program serves every seed)."""
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def base_key(words):
    k = jax.random.PRNGKey(words[0])
    return jax.random.fold_in(k, words[1])


def leaf_key(words, name: str):
    return jax.random.fold_in(base_key(words), zlib.crc32(name.encode()) & 0x7FFFFFFF)


def std_for(name: str, dims: dict) -> float:
    last = name.rsplit("/", 1)[-1]
    if last == "w_out":
        return 1.0 / math.sqrt(dims["d_ff"])
    if last == "w_o":
        return 1.0 / math.sqrt(dims["num_heads"] * dims["head_dim"])
    return 1.0 / math.sqrt(dims["d_model"])


def is_layered(name: str) -> bool:
    return name.startswith("stack/scan/")


def layer_value(words, name: str, shape, dtype, dims: dict, layer: int):
    """One layer's slice of a layered leaf (or the whole unlayered leaf when
    ``layer`` is None), in ``dtype``."""
    last = name.rsplit("/", 1)[-1]
    if last == "scale":
        return jnp.ones(shape, dtype)
    if last == "bias":
        return jnp.zeros(shape, dtype)
    key = leaf_key(words, name)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    x = jax.random.normal(key, shape, jnp.float32) * std_for(name, dims)
    return x.astype(dtype)


def leaf_value(words, name: str, shape, dtype, dims: dict):
    if not is_layered(name):
        return layer_value(words, name, shape, dtype, dims, None)
    layers = [layer_value(words, name, shape[1:], dtype, dims, l) for l in range(shape[0])]
    return jnp.stack(layers)


def path_name(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", getattr(k, "name", k)))))
    return "/".join(parts)


def make_tree(words, template, dims: dict):
    """Fill a tree of ShapeDtypeStructs (the program's own layout) leaf by
    leaf from the seed.  Call under ``jax.jit`` so the weights are made on the
    device in one program."""
    return jax.tree_util.tree_map_with_path(
        lambda p, s: leaf_value(words, path_name(p), s.shape, s.dtype, dims), template
    )


def token_batches(seed: int, n: int, rows: int, seq: int, vocab: int) -> np.ndarray:
    """``n`` batches of ``rows`` sequences of ``seq + 1`` uniform token ids:
    every row of every batch is drawn anew, so no two rows repeat."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(n, rows, seq + 1), dtype=np.int32)
