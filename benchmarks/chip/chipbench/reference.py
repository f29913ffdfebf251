"""Plain float32 reference of the served dense decoders, in ``jax.numpy``.

Written from the published architectures, not from the program: Qwen2
(arXiv:2407.10671; RMSNorm, RoPE with rotate-half, grouped-query attention
with biases on q/k/v, SwiGLU) and Mistral (arXiv:2310.06825; the same block
without biases, with a sliding-window causal mask). Every size and constant
comes from the configuration file. It imports nothing of the program.

It runs one sequence at a time, one layer at a time: each layer's bf16
weights are upcast to float32 inside the call, so the reference needs one
layer's float32 copy beside the served weights. Every matmul runs at
``Precision.HIGHEST``: on a TPU a float32 matmul is otherwise computed from
bf16 passes.

``quant`` computes the same function in a lower precision, the control of
the comparison: every matmul's operands are rounded to int8 (weights per
output column, activations per row, symmetric) or to float8 e4m3 (per
tensor), with float32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import head_dim, window

HI = jax.lax.Precision.HIGHEST
PAD = 256          # sequences are padded to a multiple of this: few shapes
QUANT_MODES = (None, "int8", "fp8")


def _round_int8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _round_fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32, or with operands rounded."""
    if quant == "int8":
        x, w = _round_int8(x, -1), _round_int8(w, 0)
    elif quant == "fp8":
        x, w = _round_fp8(x), _round_fp8(w)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, H, D): rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _layer(h, lp, conf, quant):
    S = h.shape[0]
    hq, hkv, hd = (conf["num_attention_heads"], conf["num_key_value_heads"],
                   head_dim(conf))
    eps = conf["rms_norm_eps"]
    pos = jnp.arange(S)
    a = _rms(h, lp["attn_norm"]["scale"], eps)
    at = lp["attn"]

    def proj(name, heads):
        y = _mm(a, at[name]["w"], quant)
        if "b" in at[name]:
            y = y + at[name]["b"]
        return y.reshape(S, heads, hd)

    q = _rope(proj("q", hq), pos, conf["rope_theta"])
    k = _rope(proj("k", hkv), pos, conf["rope_theta"])
    v = proj("v", hkv)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    qi, ki = pos[:, None], pos[None, :]
    mask = ki <= qi
    if window(conf):
        mask &= ki > qi - window(conf)
    scores = jnp.where(mask[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, hq * hd)
    h = h + _mm(o, at["o"]["w"], quant)
    mp = lp["mlp"]
    m = _rms(h, lp["mlp_norm"]["scale"], eps)
    g = jax.nn.silu(_mm(m, mp["gate"]["w"], quant)) * _mm(m, mp["up"]["w"], quant)
    return h + _mm(g, mp["down"]["w"], quant)


_CONF_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "rms_norm_eps", "rope_theta", "sliding_window",
              "use_sliding_window", "tie_word_embeddings")


@functools.lru_cache(maxsize=None)
def _jitted(conf_items: tuple, quant):
    conf = dict(conf_items)
    f32 = functools.partial(jax.tree.map, lambda x: x.astype(jnp.float32))

    @jax.jit
    def embed(emb, tokens):
        return emb[tokens].astype(jnp.float32)

    @jax.jit
    def layer(h, layers, i):
        lp = f32(jax.tree.map(lambda x: x[i], layers))
        return _layer(h, lp, conf, quant)

    @jax.jit
    def head(h, rows, final_scale, unembed):
        x = _rms(h[rows], final_scale.astype(jnp.float32), conf["rms_norm_eps"])
        return _mm(x, unembed.astype(jnp.float32), quant)

    return embed, layer, head


def logits_at(params, conf: dict, tokens: np.ndarray, rows: np.ndarray,
              quant=None) -> np.ndarray:
    """Float32 logits (len(rows), V) of ``tokens`` at positions ``rows``."""
    if quant not in QUANT_MODES:
        raise ValueError(f"unknown quant mode {quant!r}")
    embed, layer, head = _jitted(
        tuple((k, conf[k]) for k in _CONF_KEYS if k in conf), quant)
    S = len(tokens)
    padded = np.zeros((-(-S // PAD) * PAD,), np.int32)
    padded[:S] = tokens
    h = embed(params["embed"]["emb"], jnp.asarray(padded))
    for i in range(conf["num_hidden_layers"]):
        h = layer(h, params["layers"], i)
    unembed = (params["embed"]["emb"].T if conf["tie_word_embeddings"]
               else params["unembed"]["w"])
    return np.asarray(head(h, jnp.asarray(rows, jnp.int32),
                           params["final_norm"]["scale"], unembed))


def served_gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each served token's logit
    lies, in units of that position's logit standard deviation."""
    ref = np.asarray(ref, np.float64)
    best = ref.max(-1)
    picked = ref[np.arange(len(tokens)), tokens]
    return (best - picked) / ref.std(-1)
