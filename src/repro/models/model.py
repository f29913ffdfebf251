"""Model assembly for every assigned architecture family.

Public API (all pure functions; ``cfg`` is static):

    init_params(cfg, key, dtype, max_positions=None)      -> params pytree
    forward_train(params, cfg, batch, ...)                -> (logits, aux_loss)
    init_cache(cfg, batch_size, max_len, dtype, ...)      -> cache pytree
    prefill(params, cfg, batch, cache, ...)               -> (last_logits, cache)
    decode_step(params, cfg, tokens, cache, ...)          -> (logits, cache)

Layer stacks are stored *stacked* (leading layer dim) and executed with
``jax.lax.scan`` — one compiled layer body regardless of depth (MaxText-style),
with optional ``jax.checkpoint`` remat for training.

``batch`` dict:
    tokens: (B, S) int32                 — all families
    frames: (B, S_enc, d_model) f        — audio (STUB frontend embeddings)
    vision: (B, n_vis, d_model) f        — vlm   (STUB patch embeddings)
"""
from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as ATT
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.scan_util import layer_scan

Params = Dict[str, Any]


# ===========================================================================
# init
# ===========================================================================
def _stacked_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _dense_layer_init(cfg: ModelConfig, dtype):
    def init(key):
        ka, km = jax.random.split(key)
        p = {"attn_norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
             "attn": ATT.attn_init(ka, cfg, dtype),
             "mlp_norm": L.norm_init(cfg.norm, cfg.d_model, dtype)}
        if cfg.family == "moe":
            p["moe"] = MOE.moe_init(km, cfg, dtype)
        else:
            p["mlp"] = L.mlp_init(km, cfg.d_model, cfg.d_ff, cfg.activation, dtype)
        return p
    return init


def _encdec_layer_init(cfg: ModelConfig, dtype, *, cross: bool):
    def init(key):
        ka, kc, km = jax.random.split(key, 3)
        p = {"attn_norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
             "attn": ATT.attn_init(ka, cfg, dtype),
             "mlp_norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
             "mlp": L.mlp_init(km, cfg.d_model, cfg.d_ff, cfg.activation, dtype)}
        if cross:
            p["cross_norm"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
            p["cross"] = ATT.attn_init(kc, cfg, dtype)
        return p
    return init


def _mamba_layer_init(cfg: ModelConfig, dtype):
    def init(key):
        return {"norm": L.norm_init(cfg.norm, cfg.d_model, dtype),
                "mamba": SSM.mamba_init(key, cfg, dtype)}
    return init


def init_params(cfg: ModelConfig, key, dtype=jnp.float32,
                max_positions: Optional[int] = None) -> Params:
    """max_positions: size of learned position tables (audio decoder)."""
    ke, kl, ku, kx = jax.random.split(key, 4)
    params: Params = {"embed": L.embed_init(ke, cfg.vocab_size, cfg.d_model, dtype),
                      "final_norm": L.norm_init(cfg.norm, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(ku, cfg.d_model, cfg.vocab_size, dtype)

    if cfg.family in ("dense", "moe", "vlm"):
        params["layers"] = _stacked_init(_dense_layer_init(cfg, dtype), kl, cfg.num_layers)
    elif cfg.family == "ssm":
        params["layers"] = _stacked_init(_mamba_layer_init(cfg, dtype), kl, cfg.num_layers)
    elif cfg.family == "hybrid":
        params["layers"] = _stacked_init(_mamba_layer_init(cfg, dtype), kl, cfg.num_layers)
        params["shared_attn"] = _encdec_layer_init(cfg, dtype, cross=False)(kx)
    elif cfg.family == "audio":
        mp = max_positions or cfg.max_seq_len
        k1, k2, k3 = jax.random.split(kl, 3)
        params["enc_layers"] = _stacked_init(
            _encdec_layer_init(cfg, dtype, cross=False), k1, cfg.encoder_layers)
        params["enc_final_norm"] = L.norm_init(cfg.norm, cfg.d_model, dtype)
        params["dec_layers"] = _stacked_init(
            _encdec_layer_init(cfg, dtype, cross=True), k2, cfg.num_layers)
        params["dec_pos"] = {"emb": (jax.random.normal(k3, (mp, cfg.d_model), jnp.float32)
                                     * 0.01).astype(dtype)}
    else:
        raise ValueError(cfg.family)
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ===========================================================================
# position helpers
# ===========================================================================
def mrope_positions(cfg: ModelConfig, B: int, seq_len: int, n_vis: int,
                    start: int = 0) -> jnp.ndarray:
    """(B, seq_len, 3) position ids: vision tokens get a (t=0, h, w) grid,
    text tokens get equal (t,h,w) = grid_side + text_index (qwen2-vl style)."""
    g = max(1, int(round(n_vis ** 0.5)))
    idx = jnp.arange(seq_len) + start
    is_vis = idx < n_vis
    t = jnp.where(is_vis, 0, idx - n_vis + g)
    h = jnp.where(is_vis, idx // g, idx - n_vis + g)
    w = jnp.where(is_vis, idx % g, idx - n_vis + g)
    pos = jnp.stack([t, h, w], axis=-1)                  # (S, 3)
    return jnp.broadcast_to(pos[None], (B, seq_len, 3)).astype(jnp.int32)


def _positions(cfg: ModelConfig, B: int, S: int, n_vis: int = 0):
    if cfg.pos_emb == "mrope":
        return mrope_positions(cfg, B, S, n_vis)
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


# ===========================================================================
# logits
# ===========================================================================
def _logits(params, cfg: ModelConfig, h):
    h = L.norm_apply(cfg.norm, params["final_norm"], h)
    if cfg.tie_embeddings:
        return h @ params["embed"]["emb"].T
    return L.linear(params["unembed"], h)


# ===========================================================================
# forward (train / full sequence)
# ===========================================================================
def _dense_block(lp, cfg: ModelConfig, h, positions, *, backend, window):
    a = L.norm_apply(cfg.norm, lp["attn_norm"], h)
    h = h + ATT.self_attention(lp["attn"], cfg, a, positions=positions,
                               causal=True, window=window, backend=backend)
    m = L.norm_apply(cfg.norm, lp["mlp_norm"], h)
    if cfg.family == "moe":
        y, aux = MOE.moe_apply(lp["moe"], cfg, m)
        return h + y, aux
    return h + L.mlp_apply(lp["mlp"], m, cfg.activation), jnp.float32(0.0)


def _hybrid_segments(cfg: ModelConfig):
    """[(start, end, attn_after?)] covering all layers."""
    every = cfg.hybrid_attn_every
    segs = []
    s = 0
    while s < cfg.num_layers:
        e = min(s + every, cfg.num_layers) if every else cfg.num_layers
        segs.append((s, e, every > 0 and e - s == every))
        s = e
    return segs


def _slice_layers(stacked, a: int, b: int):
    return jax.tree.map(lambda x: x[a:b], stacked)


def _shared_attn_block(params, cfg: ModelConfig, h, positions, *, backend):
    lp = params["shared_attn"]
    a = L.norm_apply(cfg.norm, lp["attn_norm"], h)
    h = h + ATT.self_attention(lp["attn"], cfg, a, positions=positions, causal=True,
                               window=cfg.sliding_window, backend=backend)
    m = L.norm_apply(cfg.norm, lp["mlp_norm"], h)
    return h + L.mlp_apply(lp["mlp"], m, cfg.activation)


def forward_train(params: Params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray],
                  *, backend: str = "auto", remat: bool = False):
    """Full-sequence forward. Returns (logits (B, S_total, V), aux_loss)."""
    tokens = batch["tokens"]
    B, S_text = tokens.shape
    h = params["embed"]["emb"][tokens]

    if cfg.family == "vlm":
        h = jnp.concatenate([batch["vision"].astype(h.dtype), h], axis=1)
    S = h.shape[1]
    n_vis = S - S_text if cfg.family == "vlm" else 0
    positions = _positions(cfg, B, S, n_vis)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(carry, lp):
            out, aux = _dense_block(lp, cfg, carry, positions, backend=backend,
                                    window=cfg.sliding_window)
            return out, aux
        if remat:
            body = jax.checkpoint(body)
        h, auxs = layer_scan(body, h, params["layers"])
        return _logits(params, cfg, h), jnp.sum(auxs)

    if cfg.family == "ssm":
        def body(carry, lp):
            x = L.norm_apply(cfg.norm, lp["norm"], carry)
            return carry + SSM.mamba_apply(lp["mamba"], cfg, x, backend=backend), 0.0
        if remat:
            body = jax.checkpoint(body)
        h, _ = layer_scan(body, h, params["layers"])
        return _logits(params, cfg, h), jnp.float32(0.0)

    if cfg.family == "hybrid":
        def body(carry, lp):
            x = L.norm_apply(cfg.norm, lp["norm"], carry)
            return carry + SSM.mamba_apply(lp["mamba"], cfg, x, backend=backend), 0.0
        if remat:
            body = jax.checkpoint(body)
        for (a, b, attn_after) in _hybrid_segments(cfg):
            h, _ = layer_scan(body, h, _slice_layers(params["layers"], a, b))
            if attn_after:
                h = _shared_attn_block(params, cfg, h, positions, backend=backend)
        return _logits(params, cfg, h), jnp.float32(0.0)

    if cfg.family == "audio":
        enc_out = encode(params, cfg, batch["frames"], backend=backend)
        return _decode_train(params, cfg, tokens, enc_out, backend=backend, remat=remat)

    raise ValueError(cfg.family)


# --------------------------------------------------------------------- audio
def encode(params: Params, cfg: ModelConfig, frames, *, backend: str = "auto"):
    """Bidirectional encoder over stub frame embeddings (B, S_enc, d)."""
    B, S_enc, _ = frames.shape
    h = frames + L.sinusoidal_positions(S_enc, cfg.d_model).astype(frames.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(S_enc, dtype=jnp.int32)[None], (B, S_enc))

    def body(carry, lp):
        a = L.norm_apply(cfg.norm, lp["attn_norm"], carry)
        h2 = carry + ATT.self_attention(lp["attn"], cfg, a, positions=positions,
                                        causal=False, backend=backend)
        m = L.norm_apply(cfg.norm, lp["mlp_norm"], h2)
        return h2 + L.mlp_apply(lp["mlp"], m, cfg.activation), 0.0

    h, _ = layer_scan(body, h, params["enc_layers"])
    return L.norm_apply(cfg.norm, params["enc_final_norm"], h)


def _decode_train(params, cfg: ModelConfig, tokens, enc_out, *, backend, remat):
    B, S = tokens.shape
    h = params["embed"]["emb"][tokens] + params["dec_pos"]["emb"][None, :S]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(carry, lp):
        a = L.norm_apply(cfg.norm, lp["attn_norm"], carry)
        h2 = carry + ATT.self_attention(lp["attn"], cfg, a, positions=positions,
                                        causal=True, backend=backend)
        c = L.norm_apply(cfg.norm, lp["cross_norm"], h2)
        ek, ev = ATT.encode_kv(lp["cross"], cfg, enc_out)
        h2 = h2 + ATT.cross_attention(lp["cross"], cfg, c, enc_k=ek, enc_v=ev,
                                      backend=backend)
        m = L.norm_apply(cfg.norm, lp["mlp_norm"], h2)
        return h2 + L.mlp_apply(lp["mlp"], m, cfg.activation), 0.0
    if remat:
        body = jax.checkpoint(body)
    h, _ = layer_scan(body, h, params["dec_layers"])
    return _logits(params, cfg, h), jnp.float32(0.0)


# ===========================================================================
# KV / state cache
# ===========================================================================
def init_cache(cfg: ModelConfig, batch_size: int, max_len: int, dtype=jnp.float32,
               enc_len: Optional[int] = None,
               kv_quant: bool = False) -> Dict[str, jnp.ndarray]:
    """kv_quant: store K/V int8 with per-row f32 scales (dense/moe/vlm
    families) — halves (bf16) or quarters (f32) the cache residency at a
    ~1e-2 relative attention error (tested)."""
    B, hd = batch_size, cfg.resolved_head_dim
    cache: Dict[str, jnp.ndarray] = {"pos": jnp.zeros((B,), jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm"):
        kv_dtype = jnp.int8 if kv_quant else dtype
        cache["k"] = jnp.zeros((cfg.num_layers, B, cfg.num_kv_heads, max_len, hd),
                               kv_dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
        if kv_quant:
            cache["k_scale"] = jnp.zeros(
                (cfg.num_layers, B, cfg.num_kv_heads, max_len, 1), jnp.float32)
            cache["v_scale"] = jnp.zeros_like(cache["k_scale"])
    elif cfg.family in ("ssm", "hybrid"):
        s = cfg.ssm
        ch = cfg.d_inner + 2 * s.state_dim
        cache["conv"] = jnp.zeros((cfg.num_layers, B, s.conv_width - 1, ch), dtype)
        cache["ssm"] = jnp.zeros((cfg.num_layers, B, cfg.ssm_heads, s.head_dim,
                                  s.state_dim), jnp.float32)
        if cfg.family == "hybrid":
            n_attn = sum(1 for *_, a in _hybrid_segments(cfg) if a)
            cache["ak"] = jnp.zeros((n_attn, B, cfg.num_kv_heads, max_len, hd), dtype)
            cache["av"] = jnp.zeros_like(cache["ak"])
    elif cfg.family == "audio":
        el = enc_len or cfg.encoder_seq_len
        cache["k"] = jnp.zeros((cfg.num_layers, B, cfg.num_kv_heads, max_len, hd), dtype)
        cache["v"] = jnp.zeros_like(cache["k"])
        cache["ck"] = jnp.zeros((cfg.num_layers, B, cfg.num_kv_heads, el, hd), dtype)
        cache["cv"] = jnp.zeros_like(cache["ck"])
    return cache


# ===========================================================================
# paged KV cache
# ===========================================================================
# Families whose serving cache is attention K/V and therefore pageable. SSM
# and hybrid lanes carry fixed-size recurrent state (paging buys nothing);
# audio/vlm prompts carry non-token modalities the chunked path cannot split.
PAGED_FAMILIES = ("dense", "moe")

# Pool block 0 is the NULL BLOCK: never allocated, all dead block-table
# entries point at it, and writes from padded chunk rows / idle decode lanes
# are redirected into it. Readers mask by kv_len, so its contents are
# unreachable garbage by construction.
NULL_BLOCK = 0


def init_paged_cache(cfg: ModelConfig, lanes: int, num_blocks: int,
                     block_size: int, dtype=jnp.float32, *,
                     max_blocks_per_lane: Optional[int] = None,
                     kv_quant: bool = False) -> Dict[str, jnp.ndarray]:
    """Paged KV cache: one shared block pool per instance + per-lane tables.

    Layout (vLLM-style, TPU-friendly static shapes):
      kp/vp         (layers, num_blocks, Hkv, block_size, hd)  shared pool
      block_tables  (lanes, max_blocks_per_lane) int32         logical->physical
      pos           (lanes,) int32                             valid context

    Block allocation/refcounting is host-side policy (``serving.batching``);
    this pytree only carries the device state. ``kv_quant`` stores int8
    blocks with per-row f32 scale pools, as in the dense cache.
    """
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(f"paged KV cache supports families {PAGED_FAMILIES}, "
                         f"not {cfg.family!r}")
    if num_blocks < 2:
        raise ValueError("need >= 2 blocks (block 0 is the reserved null block)")
    hd = cfg.resolved_head_dim
    mb = max_blocks_per_lane if max_blocks_per_lane is not None else num_blocks
    kv_dtype = jnp.int8 if kv_quant else dtype
    cache: Dict[str, jnp.ndarray] = {
        "pos": jnp.zeros((lanes,), jnp.int32),
        "block_tables": jnp.full((lanes, mb), NULL_BLOCK, jnp.int32),
        "kp": jnp.zeros((cfg.num_layers, num_blocks, cfg.num_kv_heads,
                         block_size, hd), kv_dtype),
    }
    cache["vp"] = jnp.zeros_like(cache["kp"])
    if kv_quant:
        cache["kp_scale"] = jnp.zeros((cfg.num_layers, num_blocks,
                                       cfg.num_kv_heads, block_size, 1),
                                      jnp.float32)
        cache["vp_scale"] = jnp.zeros_like(cache["kp_scale"])
    return cache


# The block pools of a paged cache, in the order the paged attention
# functions take them; the scale pools exist only in an int8 cache.
POOL_KEYS = ("kp", "vp", "kp_scale", "vp_scale")
# The cache key under which a paged step's new rows wait for
# ``commit_paged``: each pool's rows of every layer, keyed as the pool, and
# ``at``, the (block_ids, rows) they belong at.
NEW_ROWS = "new_rows"


def _paged_layers(params: Params, cfg: ModelConfig, body, h, cache,
                  block_ids, rows):
    """Run ``body(h, pools, layer params, layer index) -> (h, new rows)``
    over the layer stack. The pools are only read, whole, at each layer's
    index: the scan neither slices them into its inputs nor restacks them
    from its outputs. Each layer's new rows come out of the scan, (layers,
    N, Hkv, ·) per pool. Returns (h, the step's change to the cache: its
    new rows under ``NEW_ROWS``, with ``block_ids``/``rows``, (N,), where
    they belong)."""
    if NEW_ROWS in cache:
        raise ValueError("the cache holds the rows of a step not yet "
                         "written: pass it through commit_paged first")
    keys = [k for k in POOL_KEYS if k in cache]
    pools = tuple(cache[k] for k in keys)
    h, new = layer_scan(
        lambda h, xs: body(h, pools, *xs), h,
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    return h, {NEW_ROWS: dict(zip(keys, new), at=(block_ids, rows))}


def commit_paged(cache, new_rows):
    """``cache`` with ``new_rows``, those of its last paged step (the
    ``NEW_ROWS`` entry the step returned), written into its pools at every
    layer: the one write to the pools. Under a donated cache
    (``InferenceEngine.commit_paged``) each pool is updated in place."""
    new = dict(new_rows)
    block_ids, rows = new.pop("at")
    cache = dict(cache)
    for key, r in new.items():
        cache[key] = ATT.write_rows(cache[key], r, block_ids, rows)
    return cache


def prefill_paged_chunk(params: Params, cfg: ModelConfig, tokens, cache, *,
                        lane, n_valid, backend: str = "auto"):
    """Prefill ONE chunk of one lane's prompt.

    tokens: (1, C) — the next C prompt tokens of ``lane`` starting at the
    lane's current ``pos`` (rows past ``n_valid`` are padding). Returns
    (logits of the LAST VALID token (1, V), the chunk's change to the
    cache): ``pos`` advanced by ``n_valid``, and the chunk's K/V rows bound
    for the lane's blocks (``NEW_ROWS``), which ``commit_paged`` writes.
    The logits only matter on the final chunk, where they seed decode
    exactly like a dense ``prefill``.
    """
    C = tokens.shape[1]
    start = cache["pos"][lane]
    n_valid = jnp.asarray(n_valid, jnp.int32)
    h = params["embed"]["emb"][tokens]
    offs = jnp.arange(C, dtype=jnp.int32)
    positions = (start + offs)[None]                           # (1, C)
    table = cache["block_tables"][lane]                        # (mb,)
    bs = cache["kp"].shape[3]
    valid = offs < n_valid
    block_ids = jnp.where(valid, table[(start + offs) // bs], NULL_BLOCK)
    rows = (start + offs) % bs
    kv_len = (start + n_valid)[None]                           # (1,)
    window = cfg.sliding_window

    def body(h, pools, lp, layer):
        a = L.norm_apply(cfg.norm, lp["attn_norm"], h)
        attn, new = ATT.paged_prefill_chunk_attention(
            lp["attn"], cfg, a, positions=positions, pools=pools, layer=layer,
            table=table, kv_len=kv_len, q_offset=start, window=window,
            backend=backend)
        h2 = h + attn
        m = L.norm_apply(cfg.norm, lp["mlp_norm"], h2)
        if cfg.family == "moe":
            # dropless routing: capacity-based dispatch sizes expert capacity
            # by the token count it sees, so per-chunk routing would drop
            # different tokens than the dense whole-prompt prefill. Dropless
            # makes chunked prefill chunk-size-invariant; it coincides with
            # the dense path exactly when its capacity never binds (e.g. the
            # dropless-capacity ``reduced()`` configs — pinned by the parity
            # tests and the CI smoke gate).
            y, _ = MOE.moe_apply(lp["moe"], cfg, m, dropless=True)
        else:
            y = L.mlp_apply(lp["mlp"], m, cfg.activation)
        return h2 + y, new

    h, update = _paged_layers(params, cfg, body, h, cache, block_ids, rows)
    update["pos"] = cache["pos"].at[lane].set(start + n_valid)
    last = jax.lax.dynamic_index_in_dim(h[0], jnp.maximum(n_valid - 1, 0), 0,
                                        keepdims=False)
    return _logits(params, cfg, last[None]), update


def decode_step_paged(params: Params, cfg: ModelConfig, tokens, cache, *,
                      live=None, backend: str = "auto"):
    """One batched decode step over every lane of a paged cache.

    tokens (lanes, 1) int32; ``live`` (lanes,) bool — lanes that are empty or
    still prefilling run the math for shape stability, but their K/V rows
    are bound for the null block and their ``pos`` does not advance (a
    freed lane's blocks may already belong to another request, so a stray
    write would corrupt it). Returns (logits (lanes, V), the step's change
    to the cache: ``pos`` and the new rows (``NEW_ROWS``), which
    ``commit_paged`` writes).
    """
    B = tokens.shape[0]
    pos = cache["pos"]
    if live is None:
        live = jnp.ones((B,), bool)
    kv_len = pos + 1
    h = params["embed"]["emb"][tokens]
    positions = pos[:, None].astype(jnp.int32)
    tables = cache["block_tables"]
    bs = cache["kp"].shape[3]
    block_ids = jnp.where(live, tables[jnp.arange(B), pos // bs], NULL_BLOCK)
    rows = pos % bs
    window = cfg.sliding_window

    def body(h, pools, lp, layer):
        a = L.norm_apply(cfg.norm, lp["attn_norm"], h)
        attn, new = ATT.paged_decode_self_attention(
            lp["attn"], cfg, a, positions=positions, pools=pools, layer=layer,
            block_tables=tables, block_ids=block_ids, rows=rows,
            kv_len=kv_len, window=window, backend=backend)
        h2 = h + attn
        m = L.norm_apply(cfg.norm, lp["mlp_norm"], h2)
        if cfg.family == "moe":
            y, _ = MOE.moe_apply(lp["moe"], cfg, m, dropless=True)
        else:
            y = L.mlp_apply(lp["mlp"], m, cfg.activation)
        return h2 + y, new

    h, update = _paged_layers(params, cfg, body, h, cache, block_ids, rows)
    update["pos"] = jnp.where(live, pos + 1, pos)
    return _logits(params, cfg, h[:, -1]), update


# ===========================================================================
# prefill
# ===========================================================================
def prefill(params: Params, cfg: ModelConfig, batch, cache, *,
            backend: str = "auto"):
    """Process the whole prompt, fill caches. Returns (last_logits (B,V), cache)."""
    tokens = batch["tokens"]
    B, S_text = tokens.shape
    h = params["embed"]["emb"][tokens]
    window = cfg.sliding_window

    if cfg.family == "vlm":
        h = jnp.concatenate([batch["vision"].astype(h.dtype), h], axis=1)
    S = h.shape[1]
    n_vis = S - S_text if cfg.family == "vlm" else 0
    positions = _positions(cfg, B, S, n_vis)

    if cfg.family in ("dense", "moe", "vlm"):
        quant = "k_scale" in cache

        def body(carry, xs):
            if quant:
                lp, kc, vc, ks, vs = xs
            else:
                lp, kc, vc = xs
                ks = vs = None
            a = L.norm_apply(cfg.norm, lp["attn_norm"], carry)
            res = ATT.prefill_attention(lp["attn"], cfg, a, positions=positions,
                                        k_cache=kc, v_cache=vc, window=window,
                                        backend=backend, k_scale=ks, v_scale=vs)
            attn, kc, vc = res[0], res[1], res[2]
            h2 = carry + attn
            m = L.norm_apply(cfg.norm, lp["mlp_norm"], h2)
            if cfg.family == "moe":
                y, _ = MOE.moe_apply(lp["moe"], cfg, m)
            else:
                y = L.mlp_apply(lp["mlp"], m, cfg.activation)
            ys = (kc, vc, res[3], res[4]) if quant else (kc, vc)
            return h2 + y, ys

        if quant:
            h, (k_new, v_new, ks_new, vs_new) = layer_scan(
                body, h, (params["layers"], cache["k"], cache["v"],
                          cache["k_scale"], cache["v_scale"]))
            cache = dict(cache, k=k_new, v=v_new, k_scale=ks_new, v_scale=vs_new,
                         pos=jnp.full((B,), S, jnp.int32))
        else:
            h, (k_new, v_new) = layer_scan(
                body, h, (params["layers"], cache["k"], cache["v"]))
            cache = dict(cache, k=k_new, v=v_new, pos=jnp.full((B,), S, jnp.int32))
        return _logits(params, cfg, h[:, -1]), cache

    if cfg.family in ("ssm", "hybrid"):
        def body(carry, xs):
            lp, _conv, _ssm = xs
            x = L.norm_apply(cfg.norm, lp["norm"], carry)
            y, conv_st, ssm_st = SSM.mamba_apply_with_state(lp["mamba"], cfg, x,
                                                            backend=backend)
            return carry + y, (conv_st, ssm_st)

        if cfg.family == "ssm":
            h, (conv_new, ssm_new) = layer_scan(
                body, h, (params["layers"], cache["conv"], cache["ssm"]))
            cache = dict(cache, conv=conv_new.astype(cache["conv"].dtype),
                         ssm=ssm_new, pos=jnp.full((B,), S, jnp.int32))
            return _logits(params, cfg, h[:, -1]), cache

        # hybrid: segments of mamba layers + shared attn blocks with their own KV
        conv_parts, ssm_parts = [], []
        ak, av = cache["ak"], cache["av"]
        attn_i = 0
        for (a, b, attn_after) in _hybrid_segments(cfg):
            h, (conv_st, ssm_st) = layer_scan(
                body, h, (_slice_layers(params["layers"], a, b),
                          cache["conv"][a:b], cache["ssm"][a:b]))
            conv_parts.append(conv_st)
            ssm_parts.append(ssm_st)
            if attn_after:
                lp = params["shared_attn"]
                x = L.norm_apply(cfg.norm, lp["attn_norm"], h)
                attn, kc, vc = ATT.prefill_attention(
                    lp["attn"], cfg, x, positions=positions, k_cache=ak[attn_i],
                    v_cache=av[attn_i], window=cfg.sliding_window, backend=backend)
                h = h + attn
                m = L.norm_apply(cfg.norm, lp["mlp_norm"], h)
                h = h + L.mlp_apply(lp["mlp"], m, cfg.activation)
                ak = ak.at[attn_i].set(kc)
                av = av.at[attn_i].set(vc)
                attn_i += 1
        cache = dict(cache,
                     conv=jnp.concatenate(conv_parts).astype(cache["conv"].dtype),
                     ssm=jnp.concatenate(ssm_parts), ak=ak, av=av,
                     pos=jnp.full((B,), S, jnp.int32))
        return _logits(params, cfg, h[:, -1]), cache

    if cfg.family == "audio":
        # encode once; precompute cross K/V; then prefill the decoder prompt
        enc_out = encode(params, cfg, batch["frames"], backend=backend)

        def cross_kv(lp):
            return ATT.encode_kv(lp["cross"], cfg, enc_out)
        _, (ck, cv) = layer_scan(lambda c, lp: (c, cross_kv(lp)), 0, params["dec_layers"])

        h = params["embed"]["emb"][tokens] + params["dec_pos"]["emb"][None, :S_text]
        dpos = jnp.broadcast_to(jnp.arange(S_text, dtype=jnp.int32)[None], (B, S_text))

        def body(carry, xs):
            lp, kc, vc, ckl, cvl = xs
            a = L.norm_apply(cfg.norm, lp["attn_norm"], carry)
            attn, kc, vc = ATT.prefill_attention(lp["attn"], cfg, a, positions=dpos,
                                                 k_cache=kc, v_cache=vc, backend=backend)
            h2 = carry + attn
            c = L.norm_apply(cfg.norm, lp["cross_norm"], h2)
            h2 = h2 + ATT.cross_attention(lp["cross"], cfg, c, enc_k=ckl, enc_v=cvl,
                                          backend=backend)
            m = L.norm_apply(cfg.norm, lp["mlp_norm"], h2)
            return h2 + L.mlp_apply(lp["mlp"], m, cfg.activation), (kc, vc)

        h, (k_new, v_new) = layer_scan(
            body, h, (params["dec_layers"], cache["k"], cache["v"],
                      ck.astype(cache["ck"].dtype), cv.astype(cache["cv"].dtype)))
        cache = dict(cache, k=k_new, v=v_new, ck=ck.astype(cache["ck"].dtype),
                     cv=cv.astype(cache["cv"].dtype),
                     pos=jnp.full((B,), S_text, jnp.int32))
        return _logits(params, cfg, h[:, -1]), cache

    raise ValueError(cfg.family)


# ===========================================================================
# decode
# ===========================================================================
def decode_step(params: Params, cfg: ModelConfig, tokens, cache, *,
                backend: str = "auto"):
    """One decode step. tokens (B, 1) int32. Returns (logits (B, V), cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]                                   # length BEFORE this token
    kv_len = pos + 1
    h = params["embed"]["emb"][tokens]
    window = cfg.sliding_window

    if cfg.pos_emb == "mrope":
        n_vis = cfg.num_vision_tokens
        g = max(1, int(round(n_vis ** 0.5)))
        p = (pos - n_vis + g).astype(jnp.int32)          # text-stream position
        positions = jnp.stack([p, p, p], axis=-1)[:, None, :]   # (B, 1, 3)
    else:
        positions = pos[:, None].astype(jnp.int32)       # (B, 1)

    if cfg.family in ("dense", "moe", "vlm"):
        quant = "k_scale" in cache

        def block(lp, hin, kc, vc, ks=None, vs=None):
            a = L.norm_apply(cfg.norm, lp["attn_norm"], hin)
            res = ATT.decode_self_attention(
                lp["attn"], cfg, a, positions=positions, k_cache=kc, v_cache=vc,
                kv_len=kv_len, window=window, backend=backend,
                k_scale=ks, v_scale=vs)
            attn, kc, vc = res[0], res[1], res[2]
            h2 = hin + attn
            m = L.norm_apply(cfg.norm, lp["mlp_norm"], h2)
            if cfg.family == "moe":
                y, _ = MOE.moe_apply(lp["moe"], cfg, m, dropless=True)
            else:
                y = L.mlp_apply(lp["mlp"], m, cfg.activation)
            if quant:
                return h2 + y, kc, vc, res[3], res[4]
            return h2 + y, kc, vc

        # Perf-iteration lever (REPRO_CACHE_MODE): with the cache as scan
        # xs/ys ("scan", baseline) XLA materializes a fresh (L,B,H,S,D) output
        # cache each step — a full copy of untouched rows. "carry" threads the
        # stacked cache through the scan carry and updates layer i in place
        # with dynamic_update_slice (XLA aliases carries in while loops), so
        # per-step cache traffic is the attention READ plus one row write.
        if os.environ.get("REPRO_CACHE_MODE", "scan") == "carry" and not quant:
            def body(carry, xs):
                hin, ck, cv = carry
                lp, i = xs
                kc = jax.lax.dynamic_index_in_dim(ck, i, 0, keepdims=False)
                vc = jax.lax.dynamic_index_in_dim(cv, i, 0, keepdims=False)
                hout, kc, vc = block(lp, hin, kc, vc)
                ck = jax.lax.dynamic_update_index_in_dim(ck, kc, i, 0)
                cv = jax.lax.dynamic_update_index_in_dim(cv, vc, i, 0)
                return (hout, ck, cv), None
            (h, k_new, v_new), _ = layer_scan(
                body, (h, cache["k"], cache["v"]),
                (params["layers"], jnp.arange(cfg.num_layers)))
        elif quant:
            def body(carry, xs):
                lp, kc, vc, ks, vs = xs
                hout, kc, vc, ks, vs = block(lp, carry, kc, vc, ks, vs)
                return hout, (kc, vc, ks, vs)
            h, (k_new, v_new, ks_new, vs_new) = layer_scan(
                body, h, (params["layers"], cache["k"], cache["v"],
                          cache["k_scale"], cache["v_scale"]))
            cache = dict(cache, k=k_new, v=v_new, k_scale=ks_new,
                         v_scale=vs_new, pos=pos + 1)
            return _logits(params, cfg, h[:, -1]), cache
        else:
            def body(carry, xs):
                lp, kc, vc = xs
                hout, kc, vc = block(lp, carry, kc, vc)
                return hout, (kc, vc)
            h, (k_new, v_new) = layer_scan(
                body, h, (params["layers"], cache["k"], cache["v"]))
        cache = dict(cache, k=k_new, v=v_new, pos=pos + 1)
        return _logits(params, cfg, h[:, -1]), cache

    if cfg.family in ("ssm", "hybrid"):
        def body(carry, xs):
            lp, conv_st, ssm_st = xs
            x = L.norm_apply(cfg.norm, lp["norm"], carry)
            y, conv_st, ssm_st = SSM.mamba_decode_step(lp["mamba"], cfg, x,
                                                       conv_st, ssm_st)
            return carry + y, (conv_st, ssm_st)

        if cfg.family == "ssm":
            h, (conv_new, ssm_new) = layer_scan(
                body, h, (params["layers"], cache["conv"], cache["ssm"]))
            cache = dict(cache, conv=conv_new.astype(cache["conv"].dtype),
                         ssm=ssm_new, pos=pos + 1)
            return _logits(params, cfg, h[:, -1]), cache

        conv_parts, ssm_parts = [], []
        ak, av = cache["ak"], cache["av"]
        attn_i = 0
        for (a, b, attn_after) in _hybrid_segments(cfg):
            h, (conv_st, ssm_st) = layer_scan(
                body, h, (_slice_layers(params["layers"], a, b),
                          cache["conv"][a:b], cache["ssm"][a:b]))
            conv_parts.append(conv_st)
            ssm_parts.append(ssm_st)
            if attn_after:
                lp = params["shared_attn"]
                x = L.norm_apply(cfg.norm, lp["attn_norm"], h)
                attn, kc, vc = ATT.decode_self_attention(
                    lp["attn"], cfg, x, positions=positions, k_cache=ak[attn_i],
                    v_cache=av[attn_i], kv_len=kv_len, window=cfg.sliding_window,
                    backend=backend)
                h = h + attn
                m = L.norm_apply(cfg.norm, lp["mlp_norm"], h)
                h = h + L.mlp_apply(lp["mlp"], m, cfg.activation)
                ak = ak.at[attn_i].set(kc)
                av = av.at[attn_i].set(vc)
                attn_i += 1
        cache = dict(cache,
                     conv=jnp.concatenate(conv_parts).astype(cache["conv"].dtype),
                     ssm=jnp.concatenate(ssm_parts), ak=ak, av=av, pos=pos + 1)
        return _logits(params, cfg, h[:, -1]), cache

    if cfg.family == "audio":
        h = h + params["dec_pos"]["emb"][pos][:, None, :]

        def body(carry, xs):
            lp, kc, vc, ckl, cvl = xs
            a = L.norm_apply(cfg.norm, lp["attn_norm"], carry)
            attn, kc, vc = ATT.decode_self_attention(
                lp["attn"], cfg, a, positions=positions, k_cache=kc, v_cache=vc,
                kv_len=kv_len, backend=backend)
            h2 = carry + attn
            c = L.norm_apply(cfg.norm, lp["cross_norm"], h2)
            h2 = h2 + ATT.cross_attention(lp["cross"], cfg, c, enc_k=ckl, enc_v=cvl,
                                          backend=backend)
            m = L.norm_apply(cfg.norm, lp["mlp_norm"], h2)
            return h2 + L.mlp_apply(lp["mlp"], m, cfg.activation), (kc, vc)

        h, (k_new, v_new) = layer_scan(
            body, h, (params["dec_layers"], cache["k"], cache["v"],
                      cache["ck"], cache["cv"]))
        cache = dict(cache, k=k_new, v=v_new, pos=pos + 1)
        return _logits(params, cfg, h[:, -1]), cache

    raise ValueError(cfg.family)
