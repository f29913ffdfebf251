"""Blockwise (flash) attention Pallas-TPU kernel for prefill.

TPU-native design notes (vs the CUDA flash-attention algorithm):
  * Grid = (B, Hq, num_q_blocks, num_k_blocks) with the K dimension innermost —
    TPU grids execute sequentially, so the online-softmax running state
    (m, l, acc) lives in VMEM scratch and persists across K iterations of the
    same (b, h, iq) triple. No atomics / warp shuffles needed.
  * Block sizes default to (block_q=128, block_k=128): MXU-aligned (128x128
    systolic array) and head_dim (64/128) rides along as the minor dim.
  * Causal + sliding-window masking is done block-wise: fully-masked K blocks
    are skipped via pl.when on the block indices (structural, known from the
    grid), in-block masking via broadcasted_iota position comparison.
  * GQA: grid iterates query heads; the K/V BlockSpec index_map maps query head
    h -> kv head h // group, so KV blocks are fetched once per group position.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  sm_scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], block_q: int, block_k: int,
                  num_k_blocks: int, q_offset: int, sk_valid: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # --- structural block skip ------------------------------------------------
    # last query position in this q block / first+last key position in k block
    q_last = iq * block_q + block_q - 1 + q_offset
    k_first = ik * block_k
    k_last = ik * block_k + block_k - 1
    live = k_first < sk_valid
    if causal:
        live &= k_first <= q_last
    if window is not None:
        # whole k block left of every query's window?
        q_first = iq * block_q + q_offset
        live &= (q_first - k_last) < window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                     # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                     # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
        kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < sk_valid
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                     # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)               # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                         # rescale old acc
        p = jnp.exp(s - m_new)                                  # (bq, bk)
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)                         # fully-masked rows
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_offset", "sk_valid",
                     "block_q", "block_k", "interpret"),
)
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None, q_offset: int = 0,
                    sk_valid: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D). Sq/Sk padded here to blocks.

    sk_valid: number of valid key positions (defaults to Sk) — keys beyond it
    are masked (used by the wrapper when padding).
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    for name, blk in (("block_q", block_q), ("block_k", block_k)):
        if blk < 1 or (blk & (blk - 1)):
            raise ValueError(f"{name} must be a positive power of two "
                             f"(MXU-aligned grid), got {blk}")
    group = Hq // Hkv
    if sk_valid is None:
        sk_valid = Sk
    sm_scale = D ** -0.5

    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sqp, Skp = Sq + pad_q, Sk + pad_k
    nq, nk = Sqp // block_q, Skp // block_k

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, num_k_blocks=nk,
        q_offset=q_offset, sk_valid=sk_valid)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq, :]
