"""Seeded model weights, owned by the benchmark.

The program under test and the plain reference both take their weights from
here, so neither depends on the other's initialisation.  Every leaf of the
program's parameter tree is drawn from its own key, ``fold_in(seed key,
crc32(path))``, so a leaf (or a layer of a stacked leaf) can be drawn again
on its own, bit for bit, by the reference.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, 64-bit seeds included."""
    s = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


def leaf_key(seed_key: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(seed_key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw(key: jax.Array, path: str, shape, dtype) -> jax.Array:
    """One leaf.  Matrices: N(0, 1/fan_in); the embedding table N(0, 0.02^2)
    (so tied-head logits have a spread near 0.64 at width 1024); norm scales
    1 + N(0, 0.1^2); biases N(0, 0.1^2)."""
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(leaf_key(key, path), shape, jnp.float32)
    if path.startswith("embed/"):
        v = z * 0.02
    elif name == "scale":
        v = 1.0 + 0.1 * z
    elif name == "b":
        v = 0.1 * z
    else:
        v = z * (shape[-2] ** -0.5)
    return v.astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def make_params(shapes, seed: int):
    """Every leaf of ``shapes`` (a pytree of ShapeDtypeStruct, e.g. from
    ``jax.eval_shape`` of the program's initialiser), made on the device in
    one jitted call."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [path_str(p) for p, _ in flat]

    @jax.jit
    def build(key):
        return [draw(key, p, s.shape, s.dtype) for p, (_, s) in zip(paths, flat)]

    return jax.tree_util.tree_unflatten(tree, build(base_key(seed)))


def calibration_tokens(seed: int, vocab: int, rows: int, length: int) -> np.ndarray:
    """The seeded calibration batch (rows, length) of token ids."""
    rng = np.random.default_rng([int(seed) % (1 << 64), 0xCA1B])
    return rng.integers(0, vocab, (rows, length), dtype=np.int32)
