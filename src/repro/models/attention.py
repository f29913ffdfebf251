"""Attention module: GQA/MQA/MHA with RoPE / M-RoPE, causal or bidirectional,
sliding window, KV-cache prefill/decode, and cross-attention (enc-dec)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.kernels.ref import dequantize_kv
from repro.models import layers as L

MROPE_SECTIONS_FRAC = (0.25, 0.375, 0.375)  # qwen2-vl [16, 24, 24] of 64 half-dims


def attn_init(key, cfg: ModelConfig, dtype, *, cross: bool = False):
    hd = cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    bias = cfg.qkv_bias
    return {
        "q": L.dense_init(kq, cfg.d_model, cfg.num_heads * hd, dtype, bias=bias),
        "k": L.dense_init(kk, cfg.d_model, cfg.num_kv_heads * hd, dtype, bias=bias),
        "v": L.dense_init(kv, cfg.d_model, cfg.num_kv_heads * hd, dtype, bias=bias),
        "o": L.dense_init(ko, cfg.num_heads * hd, cfg.d_model, dtype),
    }


def _split_heads(x, n_heads, hd):
    B, S, _ = x.shape
    return x.reshape(B, S, n_heads, hd).transpose(0, 2, 1, 3)  # (B, H, S, D)


def _merge_heads(x):
    B, H, S, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)


def quantize_kv(x, axis: int = -1):
    """Symmetric per-row int8 quantization. x (..., D) -> (int8, scale (...,1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _mrope_sections(head_dim: int):
    half = head_dim // 2
    s0 = int(half * MROPE_SECTIONS_FRAC[0])
    s1 = int(half * MROPE_SECTIONS_FRAC[1])
    return (s0, s1, half - s0 - s1)


def _position_encode(cfg: ModelConfig, q, k, positions):
    if cfg.pos_emb == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos_emb == "mrope":
        sec = _mrope_sections(cfg.resolved_head_dim)
        q = L.apply_mrope(q, positions, cfg.rope_theta, sec)
        k = L.apply_mrope(k, positions, cfg.rope_theta, sec)
    # "learned"/"none": handled at the embedding level
    return q, k


def self_attention(params, cfg: ModelConfig, x, *, positions, causal: bool = True,
                   window: Optional[int] = None, backend: str = "auto"):
    """Full-sequence self attention (train / encoder). positions: (B,S) or (B,S,3)."""
    hd = cfg.resolved_head_dim
    q = _split_heads(L.linear(params["q"], x), cfg.num_heads, hd)
    k = _split_heads(L.linear(params["k"], x), cfg.num_kv_heads, hd)
    v = _split_heads(L.linear(params["v"], x), cfg.num_kv_heads, hd)
    q, k = _position_encode(cfg, q, k, positions)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              softcap=cfg.attn_logit_softcap, backend=backend)
    return L.linear(params["o"], _merge_heads(out))


def prefill_attention(params, cfg: ModelConfig, x, *, positions, k_cache, v_cache,
                      window: Optional[int] = None, backend: str = "auto",
                      k_scale=None, v_scale=None):
    """Self attention that also writes K/V into the (zero-initialized) cache.

    x: (B, S, d); k_cache/v_cache: (B, Hkv, Smax, D) with Smax >= S.
    int8 caches (k_scale/v_scale not None) are written quantized per row.
    Returns (out, k_cache, v_cache[, k_scale, v_scale]).
    """
    hd = cfg.resolved_head_dim
    S = x.shape[1]
    q = _split_heads(L.linear(params["q"], x), cfg.num_heads, hd)
    k = _split_heads(L.linear(params["k"], x), cfg.num_kv_heads, hd)
    v = _split_heads(L.linear(params["v"], x), cfg.num_kv_heads, hd)
    q, k = _position_encode(cfg, q, k, positions)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              softcap=cfg.attn_logit_softcap, backend=backend)
    o = L.linear(params["o"], _merge_heads(out))
    if k_scale is not None:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache = jax.lax.dynamic_update_slice(k_cache, kq, (0, 0, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, vq, (0, 0, 0, 0))
        k_scale = jax.lax.dynamic_update_slice(k_scale, ks, (0, 0, 0, 0))
        v_scale = jax.lax.dynamic_update_slice(v_scale, vs, (0, 0, 0, 0))
        return o, k_cache, v_cache, k_scale, v_scale
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, 0, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, 0, 0, 0))
    return o, k_cache, v_cache


def decode_self_attention(params, cfg: ModelConfig, x, *, positions, k_cache,
                          v_cache, kv_len, window: Optional[int] = None,
                          backend: str = "auto", k_scale=None, v_scale=None):
    """One-token decode. x: (B, 1, d); kv_len (B,): length INCLUDING this token.

    The new K/V row is written at kv_len-1, then flash-decode runs over the
    cache. int8 caches (k_scale/v_scale not None) quantize the new row and
    dequantize on read. Returns (out, k_cache, v_cache[, k_scale, v_scale]).
    """
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    q = _split_heads(L.linear(params["q"], x), cfg.num_heads, hd)       # (B,H,1,D)
    k = _split_heads(L.linear(params["k"], x), cfg.num_kv_heads, hd)
    v = _split_heads(L.linear(params["v"], x), cfg.num_kv_heads, hd)
    q, k = _position_encode(cfg, q, k, positions)

    # scatter the new row at position kv_len-1 (per batch element):
    # per-batch dynamic_update_slice — fuses to an in-place write under
    # donation instead of materializing masked copies of the whole cache
    idx = (kv_len - 1).astype(jnp.int32)                                # (B,)

    def _write(cache_b, new_b, i):
        return jax.lax.dynamic_update_slice(cache_b, new_b.astype(cache_b.dtype),
                                            (jnp.int32(0), i, jnp.int32(0)))

    quant = k_scale is not None
    if quant:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache = jax.vmap(_write)(k_cache, kq, idx)
        v_cache = jax.vmap(_write)(v_cache, vq, idx)
        k_scale = jax.vmap(_write)(k_scale, ks, idx)
        v_scale = jax.vmap(_write)(v_scale, vs, idx)
        k_read = dequantize_kv(k_cache, k_scale, q.dtype)
        v_read = dequantize_kv(v_cache, v_scale, q.dtype)
    else:
        k_cache = jax.vmap(_write)(k_cache, k, idx)
        v_cache = jax.vmap(_write)(v_cache, v, idx)
        k_read, v_read = k_cache, v_cache

    out = ops.decode_attention(q, k_read, v_read, kv_len, window=window,
                               softcap=cfg.attn_logit_softcap, backend=backend)
    o = L.linear(params["o"], _merge_heads(out))
    if quant:
        return o, k_cache, v_cache, k_scale, v_scale
    return o, k_cache, v_cache


def paged_prefill_chunk_attention(params, cfg: ModelConfig, x, *, positions,
                                  pools, layer, table, kv_len, q_offset,
                                  window: Optional[int] = None,
                                  backend: str = "auto"):
    """Chunked-prefill self attention of layer ``layer`` for ONE lane of a
    paged cache.

    x: (1, C, d) — the lane's next C prompt tokens (rows at or past
    ``kv_len`` are padding). ``pools`` are the cache's (K, V[, K scale, V
    scale]) pools of every layer, each (layers, num_blocks, Hkv, block_size,
    ·), and are only read. The chunk queries attend over the lane's blocks
    of that layer, gathered through its table, with the chunk's own rows set
    at their positions, under causal masking at absolute offset
    ``q_offset`` and validity masking at ``kv_len`` (shape (1,), = q_offset
    + n_valid).

    Returns (out, the chunk's rows as the pools store them, each (C, Hkv,
    ·)); ``model.commit_paged`` writes them into the pools.
    """
    hd = cfg.resolved_head_dim
    q = _split_heads(L.linear(params["q"], x), cfg.num_heads, hd)     # (1,Hq,C,D)
    k = _split_heads(L.linear(params["k"], x), cfg.num_kv_heads, hd)
    v = _split_heads(L.linear(params["v"], x), cfg.num_kv_heads, hd)
    q, k = _position_encode(cfg, q, k, positions)
    new = _pool_rows(pools, k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))
    at = q_offset + jnp.arange(x.shape[1])
    ctx_len = table.shape[0] * pools[0].shape[3]
    at = jnp.where(at < kv_len[0], at, ctx_len)          # padding: dropped
    k_read, v_read, *scales = (
        _lane_blocks(p, layer, table)
        .at[:, :, at].set(r.transpose(1, 0, 2)[None], mode="drop")
        for p, r in zip(pools, new))
    if scales:
        k_read = dequantize_kv(k_read, scales[0], q.dtype)
        v_read = dequantize_kv(v_read, scales[1], q.dtype)
    # chunk attention runs on the masked reference path: it needs BOTH a
    # traced q_offset and kv_len masking, which the flash prefill kernel does
    # not expose; chunks are short, so the O(C * ctx) dense scores are cheap
    out = ref.mha_attention(q, k_read, v_read, causal=True, window=window,
                            softcap=cfg.attn_logit_softcap,
                            q_offset=q_offset, kv_len=kv_len)
    return L.linear(params["o"], _merge_heads(out)), new


def paged_decode_self_attention(params, cfg: ModelConfig, x, *, positions,
                                pools, layer, block_tables, kv_len,
                                window: Optional[int] = None,
                                backend: str = "auto"):
    """One-token decode of layer ``layer`` over a paged cache, batched
    across lanes.

    x: (B, 1, d); ``pools``: the cache's (K, V[, K scale, V scale]) pools of
    every layer, each (layers, num_blocks, Hkv, block_size, ·), only read;
    block_tables (B, max_blocks); kv_len (B,) length INCLUDING this token.
    The kernel reads layer ``layer`` of the K/V pools in place and this
    token's rows beside them. An int8 layer is dequantized to q's dtype
    first, and its rows are quantized and dequantized as the pools will
    hold them, so both formats run the one kernel.

    Returns (out, the new rows as the pools store them, each (B, Hkv, ·));
    ``model.commit_paged`` writes them into the pools.
    """
    hd = cfg.resolved_head_dim
    q = _split_heads(L.linear(params["q"], x), cfg.num_heads, hd)     # (B,Hq,1,D)
    k = _split_heads(L.linear(params["k"], x), cfg.num_kv_heads, hd)
    v = _split_heads(L.linear(params["v"], x), cfg.num_kv_heads, hd)
    q, k = _position_encode(cfg, q, k, positions)
    new = _pool_rows(pools, k[:, :, 0, :], v[:, :, 0, :])
    read, rows = pools, new
    if len(pools) == 4:                 # int8: one dequantized layer
        read = [dequantize_kv(_layer(p, layer), _layer(sc, layer),
                              q.dtype)[None]
                for p, sc in zip(pools[:2], pools[2:])]
        rows = [dequantize_kv(r, sc, q.dtype)
                for r, sc in zip(new[:2], new[2:])]
        layer = jnp.int32(0)
    out = ops.paged_decode_attention(
        q, *read, block_tables, kv_len, *rows, layer, window=window,
        softcap=cfg.attn_logit_softcap, backend=backend)
    return L.linear(params["o"], _merge_heads(out)), new


def _layer(pool, layer):
    return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)


def _lane_blocks(pool, layer, table):
    """One lane's blocks of layer ``layer`` of a whole-model pool, through
    its table (max_blocks,): (1, Hkv, max_blocks * block_size, ·).

    A K/V pool is read by one gather from the pool seen as (layers *
    num_blocks, ...), so its layer is never copied out whole. A per-row
    scale pool (· = 1) is tiled with its blocks minor on a TPU, where that
    view would relay the whole pool; its layer (131 KB at the served
    qwen2.5-3b shape) is sliced first."""
    if pool.shape[-1] == 1:
        return ref.gather_paged_kv(_layer(pool, layer), table[None])
    flat = pool.reshape((-1,) + pool.shape[2:])
    return ref.gather_paged_kv(flat, (layer * pool.shape[1] + table)[None])


def _pool_rows(pools, krows, vrows):
    """New K/V rows (N, Hkv, D) as ``pools`` store them: (K, V) in the pools'
    dtype, or, for the four pools of an int8 cache, int8 (K, V) and their
    per-row scales."""
    if len(pools) == 2:
        return krows.astype(pools[0].dtype), vrows.astype(pools[1].dtype)
    kq, ks = quantize_kv(krows)
    vq, vs = quantize_kv(vrows)
    return kq, vq, ks, vs


def write_rows(pool, new, block_ids, rows):
    """``pool`` (..., num_blocks, Hkv, block_size, ·) with ``new`` (..., N,
    Hkv, ·) set at (block_ids, :, rows); leading dims (a whole-model pool's
    layers) pair up one to one.

    Each (row, head) pair is its own scatter index, so the update window is
    one ·-vector that lies in the pool's own layout. With the (Hkv, ·)
    window of a ``[block_ids, :, rows]`` index, the TPU compiler gives the
    scatter a layout with the heads next to the minor dim, and relays the
    whole pool around it."""
    lead = new.shape[:-3]
    n, (N, H) = len(lead) + 2, new.shape[-3:-1]
    at = [jnp.arange(s).reshape((1,) * i + (s,) + (1,) * (n - 1 - i))
          for i, s in enumerate(lead)]
    at += [block_ids.reshape(N, 1), jnp.arange(H)[None, :],
           rows.reshape(N, 1)]
    return pool.at[tuple(at)].set(new)


def cross_attention(params, cfg: ModelConfig, x, *, enc_k, enc_v, backend: str = "auto"):
    """Decoder cross-attention over precomputed encoder K/V (B, Hkv, S_enc, D)."""
    hd = cfg.resolved_head_dim
    q = _split_heads(L.linear(params["q"], x), cfg.num_heads, hd)
    if x.shape[1] == 1:
        # decode: a (1, S_enc) score row — plain jnp is the right tool
        out = ref.mha_attention(q, enc_k, enc_v, causal=False)
    else:
        out = ops.flash_attention(q, enc_k, enc_v, causal=False, backend=backend)
    return L.linear(params["o"], _merge_heads(out))


def encode_kv(params, cfg: ModelConfig, enc_out):
    """Precompute cross-attention K/V from encoder output (once per request)."""
    hd = cfg.resolved_head_dim
    k = _split_heads(L.linear(params["k"], enc_out), cfg.num_kv_heads, hd)
    v = _split_heads(L.linear(params["v"], enc_out), cfg.num_kv_heads, hd)
    return k, v
