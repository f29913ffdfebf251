"""Public jit'd kernel wrappers with backend dispatch.

Backends:
  * "pallas"           — compiled Pallas (TPU target)
  * "pallas_interpret" — Pallas interpret mode (CPU correctness validation)
  * "ref"              — pure-jnp oracle (also what the CPU dry-run compiles;
                         identical FLOP structure to the fused kernel)
  * "auto" (default)   — pallas on TPU, ref elsewhere.

Models call these entry points only; they never touch pallas_call directly.

A tile or block parameter is an explicit kwarg, else the kernel's own
default; no cache or environment variable is read.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import ssm_scan as _ss

# A backend every call takes in place of the one it asks for. Nothing in the
# program sets it; the chip benchmark's host test of the Mistral stage
# patches it to run the Pallas kernels in interpret mode.
_FORCED: Optional[str] = None


def resolve_backend(backend: str = "auto") -> str:
    if _FORCED:
        backend = _FORCED
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None, q_offset: int = 0,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    backend: str = "auto") -> jnp.ndarray:
    b = resolve_backend(backend)
    if b == "ref":
        kw = {} if block_q is None else {"block_q": int(block_q)}
        return _ref.mha_attention_chunked(q, k, v, causal=causal, window=window,
                                          softcap=softcap, q_offset=q_offset,
                                          **kw)
    kw = {}
    if block_q is not None:
        kw["block_q"] = int(block_q)
    if block_kv is not None:
        kw["block_k"] = int(block_kv)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset,
                               interpret=(b == "pallas_interpret"), **kw)


def decode_attention(q, k_cache, v_cache, kv_len, *, window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     block_kv: Optional[int] = None,
                     backend: str = "auto") -> jnp.ndarray:
    b = resolve_backend(backend)
    if b == "ref":
        # no tunable tiles on the jnp path (block_kv is the Pallas split-KV
        # granularity); an explicit block_kv is accepted and ignored
        return _ref.decode_attention(q, k_cache, v_cache, kv_len=kv_len,
                                     window=window, softcap=softcap)
    kw = {} if block_kv is None else {"block_k": int(block_kv)}
    return _da.decode_attention(q, k_cache, v_cache, kv_len, window=window,
                                softcap=softcap,
                                interpret=(b == "pallas_interpret"), **kw)


def paged_decode_attention(q, k_pool, v_pool, block_tables, kv_len, k_new,
                           v_new, layer, *, window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           backend: str = "auto") -> jnp.ndarray:
    """Decode attention of layer ``layer`` through a paged KV cache (shared
    whole-model block pools + per-lane block tables), with this step's new
    rows passed beside the pools. See ``kernels.ref.paged_decode_attention``.
    No free tile parameter: the split-KV granularity IS the pool block size."""
    b = resolve_backend(backend)
    if b == "ref":
        return _ref.paged_decode_attention(q, k_pool, v_pool, block_tables,
                                           kv_len, k_new, v_new, layer,
                                           window=window, softcap=softcap)
    return _da.paged_decode_attention(q, k_pool, v_pool, block_tables, kv_len,
                                      k_new, v_new, layer, window=window,
                                      softcap=softcap,
                                      interpret=(b == "pallas_interpret"))


def ssd_scan(x, dt, A, Bmat, Cmat, *, chunk: int = 128,
             backend: str = "auto"):
    b = resolve_backend(backend)
    if b == "ref":
        # chunked matmul form: same algebra as the kernel, MXU-shaped FLOPs
        return _ref.ssd_scan_chunked(x, dt, A, Bmat, Cmat, chunk=chunk)
    return _ss.ssd_scan(x, dt, A, Bmat, Cmat, chunk=chunk,
                        interpret=(b == "pallas_interpret"))


def ssd_decode_step(state, x, dt, A, Bvec, Cvec):
    # single-token state update: pure jnp everywhere (elementwise + tiny matmuls,
    # no kernel win at (B,H,P,N) scale)
    return _ref.ssd_decode_step(state, x, dt, A, Bvec, Cvec)
