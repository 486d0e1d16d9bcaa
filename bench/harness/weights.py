"""Random weights made by the benchmark, on the device, from the seed.

The program's own parameter tree gives only the STRUCTURE (names and
shapes, from ``jax.eval_shape`` of its initializer); every value comes
from one jitted call here, so the same seed gives the same weights, and
the reference reads the very arrays the program serves with (laid out
for it by the family's ``plain_weights``, ``bench/families/``).

Rules by leaf name: every matrix and the attention biases N(0, std^2),
the embedding N(0, embed_std^2), norm scales 1 + N(0, 0.1^2); the two
deviations come from the configuration file's ``weights``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _path_names(path) -> tuple[str, ...]:
    out = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            out.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            out.append(str(k.idx))
    return tuple(out)


def seed_words(seed: int) -> np.ndarray:
    """Any non-negative whole seed as two uint32 words."""
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                    np.uint32)


def make_params(init_fn, seed: int, std: float, embed_std: float):
    """``init_fn(key)`` is the program's initializer; returns its tree
    filled with the benchmark's values, float32, on the default device."""
    shapes = jax.eval_shape(init_fn, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(_path_names(p), s.shape) for p, s in flat]

    @jax.jit
    def build(words):
        key = jax.random.fold_in(jax.random.key(words[0]), words[1])
        leaves = []
        for i, (names, shape) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            z = jax.random.normal(k, shape, jnp.float32)
            if names[-1] == "scale":
                leaves.append(1.0 + 0.1 * z)
            else:
                leaves.append((embed_std if names[-1] == "embed" else std) * z)
        return leaves

    leaves = build(jnp.asarray(seed_words(seed)))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def for_config(init_fn, seed: int, cfg: dict):
    """The configuration file's weights for ``seed``."""
    w = cfg["weights"]
    return make_params(init_fn, seed, float(w["std"]), float(w["embed_std"]))

