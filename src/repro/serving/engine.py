"""Inference engine: jit'd prefill / decode steps over the model zoo.

The engine owns params + compiled step functions for one architecture on one
(logical) system. Generation is greedy (argmax) by default; sampling hooks
accept a temperature. Energy/runtime accounting per request is attached via
the core analytic model so the FleetRouter can report fleet-level totals.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.scheduler import kv_blocks_needed
from repro.models import model as M


@dataclass
class GenerationResult:
    tokens: np.ndarray            # (B, n_out) generated tokens
    prompt_len: int
    steps: int


def _jit_step(fn, cfg: ModelConfig, backend: str):
    """``fn`` jitted with its configuration bound, under ``fn``'s own name:
    the profiler then shows the program as ``jit_<fn name>(...)``, where a
    bare ``functools.partial`` shows as ``jit__unknown(...)``."""
    return jax.jit(functools.update_wrapper(
        functools.partial(fn, cfg=cfg, backend=backend), fn))


class InferenceEngine:
    """Single-model engine with a fixed max context and batch size."""

    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 512,
                 backend: str = "auto", dtype=jnp.float32,
                 kv_quant: bool = False):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.backend = backend
        self.dtype = dtype
        self.kv_quant = kv_quant
        self._prefill = _jit_step(M.prefill, cfg, backend)
        self._decode = _jit_step(M.decode_step, cfg, backend)
        self._prefill_chunk = _jit_step(M.prefill_paged_chunk, cfg, backend)
        self._decode_paged = _jit_step(M.decode_step_paged, cfg, backend)
        # the one program that writes the paged pools; its cache is donated,
        # so the pools are updated in place
        self._commit = jax.jit(M.commit_paged, donate_argnums=0)

    # ------------------------------------------------------------------ api
    def new_cache(self, batch_size: int):
        return M.init_cache(self.cfg, batch_size, self.max_len, self.dtype,
                            enc_len=self.cfg.encoder_seq_len or None,
                            kv_quant=self.kv_quant)

    def new_paged_cache(self, lanes: int, num_blocks: int, block_size: int):
        """Paged cache sized so one lane can hold up to ``max_len`` context."""
        mb = kv_blocks_needed(self.max_len, block_size)
        return M.init_paged_cache(self.cfg, lanes, num_blocks, block_size,
                                  self.dtype, max_blocks_per_lane=mb,
                                  kv_quant=self.kv_quant)

    def prefill_chunk(self, tokens: jnp.ndarray, cache, lane: int, n_valid: int):
        """Chunked prefill of one lane (see ``model.prefill_paged_chunk``).
        ``lane``/``n_valid`` trace as 0-d arrays: one compilation per chunk
        shape, not per lane or valid count. Returns (logits, cache), the
        chunk's rows waiting in the cache for ``commit_paged``; the cache
        passed in is left as it was."""
        logits, update = self._prefill_chunk(
            params=self.params, tokens=tokens, cache=cache, lane=lane,
            n_valid=n_valid)
        return logits, dict(cache, **update)

    def decode_paged(self, tokens: jnp.ndarray, cache, live: jnp.ndarray):
        """One decode step of every lane (see ``model.decode_step_paged``).
        Returns (logits, cache), the step's rows waiting in the cache for
        ``commit_paged``; the cache passed in is left as it was."""
        logits, update = self._decode_paged(params=self.params, tokens=tokens,
                                            cache=cache, live=live)
        return logits, dict(cache, **update)

    def commit_paged(self, cache):
        """``cache`` with the rows of its last paged step written into its
        pools in place (``model.commit_paged``): the cache passed in is
        donated, and its buffers are deleted. A cache with no rows waiting
        is returned as it is."""
        if M.NEW_ROWS not in cache:
            return cache
        rest = {k: v for k, v in cache.items() if k != M.NEW_ROWS}
        return self._commit(rest, cache[M.NEW_ROWS])

    def prefill(self, batch: Dict[str, jnp.ndarray], cache=None):
        B = batch["tokens"].shape[0]
        if cache is None:
            cache = self.new_cache(B)
        logits, cache = self._prefill(params=self.params, batch=batch, cache=cache)
        return logits, cache

    def decode(self, tokens: jnp.ndarray, cache):
        return self._decode(params=self.params, tokens=tokens, cache=cache)

    def generate(self, batch: Dict[str, jnp.ndarray], max_new_tokens: int = 32,
                 *, temperature: float = 0.0, key=None,
                 eos_id: Optional[int] = None) -> GenerationResult:
        """Greedy (or sampled) generation. All requests share prompt length.

        With temperature > 0 and no explicit key, a fixed seeded PRNGKey is
        used so sampled generation is reproducible by default (previously
        key=None crashed inside jax.random.fold_in).
        """
        if temperature > 0.0 and key is None:
            key = jax.random.PRNGKey(0)
        B, S = batch["tokens"].shape
        logits, cache = self.prefill(batch)
        out = []
        tok = self._select(logits, temperature, key, 0)
        out.append(tok)
        for i in range(max_new_tokens - 1):
            logits, cache = self.decode(tok[:, None], cache)
            tok = self._select(logits, temperature, key, i + 1)
            out.append(tok)
            # deliberate per-token sync: early EOS exit saves whole decode
            # steps, which dwarfs the transfer cost at batch scale
            if eos_id is not None and bool(  # repro-lint: allow[jax-host-sync]
                    jnp.all(tok == eos_id)):
                break
        toks = np.stack([np.asarray(t) for t in out], axis=1)
        return GenerationResult(tokens=toks, prompt_len=S, steps=toks.shape[1])

    @staticmethod
    def _select(logits, temperature, key, step):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        k = jax.random.fold_in(key, step)
        return jax.random.categorical(k, logits / temperature).astype(jnp.int32)
