"""Pure-jnp oracles for every Pallas kernel.

These are the semantic ground truth: each kernel's test sweeps shapes/dtypes and
asserts allclose against these functions. They are also the fallback execution
path on non-TPU backends (the dry-run compiles these — same FLOP structure).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _attn_mask(sq: int, sk: int, *, causal: bool, window: Optional[int],
               q_offset: int = 0) -> jnp.ndarray:
    """(sq, sk) boolean mask. q position i attends to k position j iff
    j <= i+q_offset (causal) and i+q_offset - j < window (sliding window)."""
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
    return mask


def mha_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, window: Optional[int] = None,
                  softcap: Optional[float] = None, q_offset: int = 0,
                  kv_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Reference grouped-query attention.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) with Hq % Hkv == 0.
    Computation in f32, returns q.dtype.
    kv_len: optional (B,) valid KV lengths (entries >= kv_len are masked).
    """
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    qf = q.astype(jnp.float32) * (D ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(B, Hkv, group, Sq, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf)
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    Sk = k.shape[2]
    mask = _attn_mask(Sq, Sk, causal=causal, window=window, q_offset=q_offset)
    if kv_len is not None:
        valid = jnp.arange(Sk)[None, :] < kv_len[:, None]      # (B, Sk)
        mask = mask[None, :, :] & valid[:, None, :]            # (B, Sq, Sk)
        logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    else:
        logits = jnp.where(mask[None, None, None, :, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
    return out.reshape(B, Hq, Sq, D).astype(q.dtype)


def mha_attention_chunked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                          causal: bool = True, window: Optional[int] = None,
                          softcap: Optional[float] = None, q_offset: int = 0,
                          block_q: int = 1024) -> jnp.ndarray:
    """Query-chunked attention in pure jnp: O(block_q * Sk) temporaries instead
    of O(Sq * Sk). Execution path for long prefills on non-TPU backends (the
    Pallas kernel covers TPU); numerically identical to ``mha_attention``.
    """
    from repro.models.scan_util import layer_scan  # unroll control

    B, Hq, Sq, D = q.shape
    if Sq <= block_q:
        return mha_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, q_offset=q_offset)
    pad = (-Sq) % block_q
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q
    nq = qp.shape[2] // block_q
    qblocks = jnp.moveaxis(qp.reshape(B, Hq, nq, block_q, D), 2, 0)

    def body(i, qb):
        out = mha_attention(qb, k, v, causal=causal, window=window,
                            softcap=softcap, q_offset=q_offset + i * block_q)
        return i + 1, out

    _, outs = layer_scan(body, 0, qblocks)
    out = jnp.moveaxis(outs, 0, 2).reshape(B, Hq, nq * block_q, D)
    return out[:, :, :Sq, :]


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray, *,
                     kv_len: jnp.ndarray, softcap: Optional[float] = None,
                     window: Optional[int] = None) -> jnp.ndarray:
    """Single-token decode attention against a (possibly partially filled) cache.

    q: (B, Hq, 1, D); caches: (B, Hkv, Smax, D); kv_len: (B,) number of valid
    positions (the new token's own K/V must already be written at kv_len-1).

    Sliding-window fast path: when the window is much smaller than the cache,
    only the last `window` rows are gathered (per batch element) before the
    dense attention — so compute AND memory traffic scale with the window,
    matching the Pallas kernel's structural block skip.
    """
    B, Hq, _, D = q.shape
    Smax = k_cache.shape[2]
    if window is not None and Smax > 2 * window:
        w = window
        start = jnp.clip(kv_len - w, 0, Smax - w).astype(jnp.int32)    # (B,)
        sl = lambda c, s: jax.lax.dynamic_slice_in_dim(c, s, w, axis=1)
        k_win = jax.vmap(sl)(k_cache, start)
        v_win = jax.vmap(sl)(v_cache, start)
        return decode_attention(q, k_win, v_win, kv_len=kv_len - start,
                                softcap=softcap, window=None)
    q_offset = 0  # positions handled through kv_len masking
    Hkv = k_cache.shape[1]
    group = Hq // Hkv
    qf = q.astype(jnp.float32) * (D ** -0.5)
    qg = qf.reshape(B, Hkv, group, 1, D)
    logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k_cache.astype(jnp.float32))
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    kpos = jnp.arange(Smax)[None, :]
    valid = kpos < kv_len[:, None]
    if window is not None:
        valid &= kpos >= (kv_len[:, None] - window)
    logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, Hq, 1, D).astype(q.dtype)


def gather_paged_kv(pool: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Materialize a contiguous per-lane view of a paged KV pool.

    pool: (num_blocks, Hkv, block_size, D) shared block pool;
    block_tables: (B, max_blocks) int32 per-lane block indices (entries past a
    lane's live length may point anywhere valid — typically the reserved null
    block 0 — since downstream attention masks by kv_len).
    Returns (B, Hkv, max_blocks * block_size, D).
    """
    g = pool[block_tables]                       # (B, mb, Hkv, bs, D)
    B, mb, Hkv, bs, D = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, mb * bs, D)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray, *,
                           kv_len: jnp.ndarray, softcap: Optional[float] = None,
                           window: Optional[int] = None) -> jnp.ndarray:
    """Single-token decode attention reading K/V through block tables.

    q: (B, Hq, 1, D); pools: (num_blocks, Hkv, block_size, D);
    block_tables: (B, max_blocks) int32; kv_len: (B,) valid positions per lane
    (the new token's K/V must already be written into its block at kv_len-1).
    Semantic ground truth for the Pallas paged kernel: gather the lane's
    blocks into a contiguous cache view, then run dense masked decode.
    """
    k = gather_paged_kv(k_pool, block_tables)
    v = gather_paged_kv(v_pool, block_tables)
    return decode_attention(q, k, v, kv_len=kv_len, softcap=softcap,
                            window=window)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of the models' symmetric per-row int8 KV quantization."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def paged_decode_attention_quant(q: jnp.ndarray, k_pool: jnp.ndarray,
                                 v_pool: jnp.ndarray, k_scale_pool: jnp.ndarray,
                                 v_scale_pool: jnp.ndarray,
                                 block_tables: jnp.ndarray, *,
                                 kv_len: jnp.ndarray,
                                 softcap: Optional[float] = None,
                                 window: Optional[int] = None) -> jnp.ndarray:
    """Decode attention over int8-quantized paged K/V — gather-dequant oracle.

    q: (B, Hq, 1, D); k_pool/v_pool: (num_blocks, Hkv, block_size, D) int8;
    k_scale_pool/v_scale_pool: (num_blocks, Hkv, block_size, 1) f32 per-row
    scales. The oracle of the int8 paged read in ``models.attention``: gather
    the lane's blocks, dequantize to q.dtype, then dense masked decode.
    """
    k = dequantize_kv(gather_paged_kv(k_pool, block_tables),
                      gather_paged_kv(k_scale_pool, block_tables), q.dtype)
    v = dequantize_kv(gather_paged_kv(v_pool, block_tables),
                      gather_paged_kv(v_scale_pool, block_tables), q.dtype)
    return decode_attention(q, k, v, kv_len=kv_len, softcap=softcap,
                            window=window)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bmat: jnp.ndarray, Cmat: jnp.ndarray,
             init_state: Optional[jnp.ndarray] = None,
             ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Reference Mamba2 SSD recurrence (exact sequential scan).

    x:    (B, H, S, P)   per-head inputs
    dt:   (B, H, S)      softplus-activated step sizes (>0)
    A:    (H,)           negative decay rates (A < 0)
    Bmat: (B, S, N)      input projection onto state (shared across heads, ngroups=1)
    Cmat: (B, S, N)      state readout
    init_state: (B, H, P, N) or None.
    Returns (y, final_state): y (B, H, S, P), final_state (B, H, P, N).

    Recurrence per head:  state_t = exp(dt_t * A) * state_{t-1} + dt_t * x_t B_t^T
                          y_t = state_t C_t
    """
    Bsz, H, S, P = x.shape
    N = Bmat.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = Bmat.astype(jnp.float32)
    Cf = Cmat.astype(jnp.float32)
    state0 = (jnp.zeros((Bsz, H, P, N), jnp.float32) if init_state is None
              else init_state.astype(jnp.float32))

    def step(state, inputs):
        xt, dtt, Bt, Ct = inputs           # (B,H,P), (B,H), (B,N), (B,N)
        decay = jnp.exp(dtt * Af[None, :])                      # (B,H)
        upd = jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], Bt)
        state = state * decay[..., None, None] + upd
        yt = jnp.einsum("bhpn,bn->bhp", state, Ct)
        return state, yt

    xs = (jnp.moveaxis(xf, 2, 0), jnp.moveaxis(dtf, 2, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    final, ys = jax.lax.scan(step, state0, xs)
    y = jnp.moveaxis(ys, 0, 2)             # (B, H, S, P)
    return y.astype(x.dtype), final


def ssd_scan_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                     Bmat: jnp.ndarray, Cmat: jnp.ndarray, *, chunk: int = 128,
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD in pure jnp — same algebra as the Pallas kernel (matmul
    form, MXU-shaped FLOPs), used as the execution path on non-TPU backends.
    The sequential ``ssd_scan`` above remains the test oracle for both.
    """
    B, H, S, P = x.shape
    N = Bmat.shape[-1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, 0), (0, pad)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk
    xf = x.astype(jnp.float32).reshape(B, H, nc, chunk, P)
    dtf = dt.astype(jnp.float32).reshape(B, H, nc, chunk)
    Af = A.astype(jnp.float32)
    Bf = Bmat.astype(jnp.float32).reshape(B, nc, chunk, N)
    Cf = Cmat.astype(jnp.float32).reshape(B, nc, chunk, N)

    g = dtf * Af[None, :, None, None]                    # (B,H,nc,L)
    cum = jnp.cumsum(g, axis=-1)
    seg = cum[..., :, None] - cum[..., None, :]          # (B,H,nc,L,L)
    ii = jnp.arange(chunk)
    causal = ii[:, None] >= ii[None, :]
    # clamp BEFORE exp: masked (j > i) entries have seg > 0 and can overflow
    # to inf, and the backward of where() would turn inf * 0 into NaN
    seg = jnp.where(causal, seg, 0.0)
    decay = jnp.where(causal, jnp.exp(seg), 0.0)
    cb = jnp.einsum("bcln,bcmn->bclm", Cf, Bf)           # (B,nc,L,L)
    att = cb[:, None] * decay * dtf[..., None, :]        # (B,H,nc,L,L)
    y_intra = jnp.einsum("bhclm,bhcmp->bhclp", att, xf)

    # inter-chunk state carry (scan over nc chunks)
    total = cum[..., -1]                                 # (B,H,nc)
    w = jnp.exp(total[..., None] - cum) * dtf            # (B,H,nc,L)
    chunk_state = jnp.einsum("bhclp,bcln->bhcpn", xf * w[..., None], Bf)  # per-chunk update

    def carry(state, inp):
        tot_c, upd_c = inp                               # (B,H), (B,H,P,N)
        new = state * jnp.exp(tot_c)[..., None, None] + upd_c
        return new, state                                # emit the INCOMING state

    state0 = jnp.zeros((B, H, P, N), jnp.float32)
    final, in_states = jax.lax.scan(
        carry, state0,
        (jnp.moveaxis(total, 2, 0), jnp.moveaxis(chunk_state, 2, 0)))
    in_states = jnp.moveaxis(in_states, 0, 2)            # (B,H,nc,P,N)
    y_inter = jnp.exp(cum)[..., None] * jnp.einsum("bcln,bhcpn->bhclp", Cf, in_states)
    y = (y_intra + y_inter).reshape(B, H, Sp, P)[:, :, :S, :]
    return y.astype(x.dtype), final


def ssd_decode_step(state: jnp.ndarray, x: jnp.ndarray, dt: jnp.ndarray,
                    A: jnp.ndarray, Bvec: jnp.ndarray, Cvec: jnp.ndarray,
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One-token SSD state update. state (B,H,P,N), x (B,H,P), dt (B,H),
    Bvec/Cvec (B,N). Returns (y (B,H,P), new_state)."""
    sf = state.astype(jnp.float32)
    decay = jnp.exp(dt.astype(jnp.float32) * A.astype(jnp.float32)[None, :])
    upd = jnp.einsum("bhp,bn->bhpn", x.astype(jnp.float32) * dt[..., None], Bvec.astype(jnp.float32))
    new = sf * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new, Cvec.astype(jnp.float32))
    return y.astype(x.dtype), new.astype(state.dtype)
