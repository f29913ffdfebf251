"""Seeded weights for a configuration, made on the device in one jitted call,
in the layout the program's dense decoder reads and the type it serves.

The reference reads these same arrays: they are made here, by the
benchmark, not by the program. Every parameter is drawn, biases and norm
scales too, so a program that dropped one would show.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import head_dim

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def seed_key(seed: int, stream: int = 0) -> jnp.ndarray:
    """A raw threefry key from any whole-number seed, however large."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _layout(conf: dict):
    """(path, shape, kind) of every leaf; kind picks the distribution."""
    d, f, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    L, hd = conf["num_hidden_layers"], head_dim(conf)
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    leaves = [(("embed", "emb"), (V, d), "embed"),
              (("final_norm", "scale"), (d,), "scale")]
    if not conf["tie_word_embeddings"]:
        leaves.append((("unembed", "w"), (d, V), "matrix"))
    lay = [(("attn_norm", "scale"), (L, d), "scale"),
           (("mlp_norm", "scale"), (L, d), "scale"),
           (("attn", "q", "w"), (L, d, hq * hd), "matrix"),
           (("attn", "k", "w"), (L, d, hkv * hd), "matrix"),
           (("attn", "v", "w"), (L, d, hkv * hd), "matrix"),
           (("attn", "o", "w"), (L, hq * hd, d), "matrix"),
           (("mlp", "gate", "w"), (L, d, f), "matrix"),
           (("mlp", "up", "w"), (L, d, f), "matrix"),
           (("mlp", "down", "w"), (L, f, d), "matrix")]
    if conf["attention_bias"]:
        lay += [(("attn", x, "b"), (L, n), "bias")
                for x, n in (("q", hq * hd), ("k", hkv * hd), ("v", hkv * hd))]
    leaves += [(("layers",) + p, s, k) for p, s, k in lay]
    return leaves


def _draw(key, shape, kind):
    if kind == "matrix":                  # fan-in scaling keeps h at O(1)
        return _normal(key, shape, shape[-2] ** -0.5)
    if kind == "scale":
        return 1.0 + _normal(key, shape, 0.1)
    if kind == "bias":
        return _normal(key, shape, 0.1)
    # embedding rows at 1/sqrt(d), as trained embeddings sit: a tied head
    # then reads what the layers wrote, not an echo of the input token
    return _normal(key, shape, shape[-1] ** -0.5)


@functools.lru_cache(maxsize=None)
def _maker(conf_items: tuple, dtype_name: str):
    conf = dict(conf_items)
    dtype = DTYPES[dtype_name]
    layout = _layout(conf)

    def make(key):
        tree: dict = {}
        for i, (path, shape, kind) in enumerate(layout):
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = _draw(jax.random.fold_in(key, i), shape,
                                   kind).astype(dtype)
        return tree

    return jax.jit(make)


_SHAPE_KEYS = ("hidden_size", "intermediate_size", "vocab_size",
               "num_hidden_layers", "num_attention_heads",
               "num_key_value_heads", "head_dim", "tie_word_embeddings",
               "attention_bias")


def make_params(conf: dict, seed: int, dtype_name: str):
    items = tuple((k, conf[k]) for k in _SHAPE_KEYS if k in conf)
    return _maker(items, dtype_name)(seed_key(seed))
