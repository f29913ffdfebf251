"""Flash-decode Pallas-TPU kernel: one new query token vs a long KV cache.

TPU-native adaptation of flash-decode (no warp-level reductions):
  * Grouped-query packing: the G = Hq/Hkv query heads sharing one KV head form
    the *rows* of the query block, so the MXU sees a (G, D) x (D, bk) matmul
    instead of a degenerate (1, D) one. This is the standard TPU trick for
    making single-token decode MXU-friendly.
  * Split-KV: the cache is scanned in block_k chunks along the innermost
    (sequential) grid dimension; online-softmax partials (m, l, acc) persist in
    VMEM scratch exactly as in the prefill kernel, and blocks entirely beyond
    kv_len (or left of the sliding window) are skipped structurally.
  * kv_len is a scalar-prefetch operand (SMEM) so per-batch lengths steer the
    block skip without touching the vector units.
  * Paged (``paged_decode_attention``): one grid step per lane, which DMAs
    only the lane's live pool blocks, all KV heads at once, through its
    scalar-prefetched block table, double-buffered in groups of 128 tokens.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   sm_scale: float, window: Optional[int],
                   softcap: Optional[float], block_k: int, num_k_blocks: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    kv_len = kv_len_ref[b]

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_first = ik * block_k
    live = k_first < kv_len
    if window is not None:
        k_last = k_first + block_k - 1
        live &= k_last >= (kv_len - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                     # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        G = s.shape[0]
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (G, block_k), 1)
        mask = kpos < kv_len
        if window is not None:
            mask &= kpos >= (kv_len - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_decode_kernel(kv_len_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref,
                         *, sm_scale: float, window: Optional[int],
                         softcap: Optional[float], block_size: int,
                         group: int):
    """One grid step per lane: walk the lane's live blocks only.

    The live range of lane ``b`` is blocks ``[lo, hi)``: ``hi`` covers its
    ``kv_len`` positions and ``lo`` is the first block inside the window.
    The range is walked in groups of ``group`` blocks. Each block is one DMA
    of its whole ``(Hkv, bs, D)`` tile from the pool, all heads at once,
    into slot ``i`` of a two-slot VMEM buffer laid out ``(Hkv, group * bs,
    D)``, so a group is scored for every head by one batched matmul. Group
    ``g + 1`` (or the next live lane's first group) is started before group
    ``g`` is computed. Buffer rows past ``hi`` are not loaded: they hold
    earlier blocks (zeros before the first) and are masked by position, as
    rows past ``kv_len`` inside the last block are."""
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    max_blocks = tables_ref.shape[1]

    def live_range(lane):
        n = kv_len_ref[lane]
        lo = 0 if window is None else jnp.maximum(n - window, 0) // block_size
        hi = (n + block_size - 1) // block_size
        return n, lo, hi

    def dmas(lane, g, slot, act):
        """``act`` ("start" or "wait") on the K and V copies of the blocks
        of lane's group g that lie before its ``hi``."""
        _, lo, hi = live_range(lane)
        for i in range(group):
            j = lo + g * group + i
            blk = tables_ref[lane, jnp.minimum(j, max_blocks - 1)]
            rows = pl.ds(i * block_size, block_size)
            pair = [pltpu.make_async_copy(pool.at[blk], buf.at[slot, :, rows],
                                          sems.at[p, slot])
                    for p, (pool, buf) in enumerate(((k_hbm, k_buf),
                                                     (v_hbm, v_buf)))]

            @pl.when(j < hi)
            def _(pair=pair):
                for c in pair:
                    getattr(c, act)()

    @pl.when(b == 0)
    def _init():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0          # slot of the next group to compute
        slot_ref[1] = 0          # 1 once that group has been started

    n, lo, hi = live_range(b)
    groups = (hi - lo + group - 1) // group

    @pl.when(groups == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(groups > 0)
    def _walk():
        nxt = jax.lax.fori_loop(
            b + 1, lanes,
            lambda i, c: jnp.where((c == lanes) & (kv_len_ref[i] > 0), i, c),
            lanes)
        first = slot_ref[0]

        @pl.when(slot_ref[1] == 0)
        def _prime():
            dmas(b, 0, first, "start")

        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        q = q_ref[0].astype(jnp.float32) * sm_scale             # (Hkv, G, D)

        def body(g, carry):
            slot = (first + g) % 2

            @pl.when(g + 1 < groups)
            def _next_group():
                dmas(b, g + 1, 1 - slot, "start")

            @pl.when((g + 1 == groups) & (nxt < lanes))
            def _next_lane():
                dmas(nxt, 0, 1 - slot, "start")

            dmas(b, g, slot, "wait")
            k = k_buf[slot].astype(jnp.float32)     # (Hkv, group * bs, D)
            v = v_buf[slot].astype(jnp.float32)
            s = jnp.einsum("hgd,hkd->hgk", q, k,    # (Hkv, G, group * bs)
                           preferred_element_type=jnp.float32)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            kpos = (lo + g * group) * block_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 2)
            mask = kpos < n
            if window is not None:
                mask &= kpos >= (n - window)
            s = jnp.where(mask, s, NEG_INF)

            m_prev, l_prev = m_ref[...], l_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            l_ref[...] = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
                "hgk,hkd->hgd", p, v, preferred_element_type=jnp.float32)
            m_ref[...] = m_new
            return carry

        jax.lax.fori_loop(0, groups, body, 0)
        slot_ref[0] = (first + groups) % 2
        slot_ref[1] = (nxt < lanes).astype(jnp.int32)
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret"))
def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           kv_len: jnp.ndarray, *,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Flash-decode over a paged KV cache.

    q: (B, Hq, 1, D); pools: (num_blocks, Hkv, block_size, D);
    block_tables: (B, max_blocks) int32 — entry j is the pool block holding
    lane b's positions [j*block_size, (j+1)*block_size); entries past a
    lane's context must still be valid indices (the batcher points them at
    the reserved null block 0). kv_len: (B,) int32; a lane with kv_len 0
    gets zeros. Returns (B, Hq, 1, D).

    The grid is one step per lane. kv_len and the block table ride in SMEM
    via scalar prefetch; the pools stay in HBM and each step DMAs only the
    blocks of the lane's live range, ``group`` blocks (128 tokens) at a
    time, double-buffered across groups and across lanes (see
    ``_paged_decode_kernel``). No copy of the cache is made.
    """
    B, Hq, one, D = q.shape
    assert one == 1
    _, Hkv, block_size, _ = k_pool.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    group = max(1, 128 // block_size)

    qg = q.reshape(B, Hkv, G, D)
    kv_len = kv_len.astype(jnp.int32)
    block_tables = block_tables.astype(jnp.int32)

    kernel = functools.partial(
        _paged_decode_kernel, sm_scale=D ** -0.5, window=window,
        softcap=softcap, block_size=block_size, group=group)
    buf = pltpu.VMEM((2, Hkv, group * block_size, D), k_pool.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, *_: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            buf, buf,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(kv_len, block_tables, qg, k_pool, v_pool)
    return out.reshape(B, Hq, 1, D)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "block_k", "interpret"),
)
def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     kv_len: jnp.ndarray, *, window: Optional[int] = None,
                     softcap: Optional[float] = None, block_k: int = 128,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, 1, D); caches (B, Hkv, Smax, D); kv_len (B,) int32.

    Returns (B, Hq, 1, D). The new token's K/V must already be written into the
    cache at position kv_len-1.
    """
    B, Hq, one, D = q.shape
    assert one == 1
    _, Hkv, Smax, _ = k_cache.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    sm_scale = D ** -0.5

    pad_k = (-Smax) % block_k
    if pad_k:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Skp = Smax + pad_k
    nk = Skp // block_k

    # grouped-query packing: (B, Hkv, G, D)
    qg = q.reshape(B, Hkv, G, D)
    kv_len = kv_len.astype(jnp.int32)

    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, window=window, softcap=softcap,
        block_k=block_k, num_k_blocks=nk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ik, *_: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ik, *_: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ik, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(kv_len, qg, k_cache, v_cache)
    return out.reshape(B, Hq, 1, D)
