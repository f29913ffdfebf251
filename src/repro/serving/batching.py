"""Continuous batching: fixed-slot dense loop and the paged-KV runtime.

Two engines loops share one Request/queue interface:

  * ``ContinuousBatcher`` — the original dense loop: ``slots`` decode lanes
    over one ``(layers, slots, heads, max_len, hd)`` cache; finished lanes
    are refilled by whole-prompt prefill into a spliced lane region.
  * ``PagedContinuousBatcher`` — vLLM-style paged runtime: a shared block
    pool + per-lane block tables (``model.init_paged_cache``), with

      - **memory-aware admission**: a request is admitted only when its
        worst-case context (prompt + token budget) fits in free blocks, so
        "how many requests fit" is governed by KV memory, not the slot count;
      - **chunked prefill**: a queued prompt enters ``chunk`` tokens per tick
        into its blocks while resident lanes keep decoding — a long prompt no
        longer stalls the whole loop;
      - **prefix-block sharing**: full prompt blocks are content-addressed
        and refcounted, so n requests sharing a prompt prefix hold one
        physical copy of its K/V.

This module is deliberately single-model; cross-pool routing lives in
``router.py`` (the paper's scheduler).

The paged runtime names its host work in the profiler's trace with the
spans in ``SPANS`` (``jax.profiler.TraceAnnotation``: a no-op object when
no profile is being taken). A tick is ``batcher.step``; every device op it
enqueues is enqueued inside its ``batcher.admit``, ``batcher.prefill``,
``batcher.decode`` or ``batcher.retire`` child, each blocking device->host
copy is a ``batcher.sync`` child, and the rest of the tick is host
bookkeeping. Stats (``rid``, ``tokens``, ``lanes``, ``blocks``, ``phase``)
are known when the span opens; ``rid`` ties one request's spans together.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.scheduler import kv_blocks_needed
from repro.models import model as M
from repro.models.model import NULL_BLOCK
from repro.serving.engine import InferenceEngine

# Every span the serving path writes; ``FleetRouter.submit`` writes the first.
ROUTER_SUBMIT = "router.submit"
SPANS = (ROUTER_SUBMIT, "batcher.step", "batcher.admit", "batcher.prefill",
         "batcher.decode", "batcher.sync", "batcher.retire")


@dataclass
class Request:
    rid: int
    tokens: np.ndarray              # (m,) prompt
    max_new_tokens: int
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    eos_id: Optional[int] = None    # stop early when this token is emitted
    hold: bool = False              # prefill only; decode waits for a handoff
    # ``time.perf_counter()`` at submit and when the request took a lane
    queued_s: Optional[float] = None
    admitted_s: Optional[float] = None


class _BatcherBase:
    """Queue/lane state and the tick loop shared by both runtimes. The
    EOS-retirement predicate in particular must stay ONE definition — the
    dense/paged token-parity gate depends on identical completion rules."""

    def __init__(self, engine: InferenceEngine, slots: int):
        self.engine = engine
        self.slots = slots
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * slots
        self._last_tok = jnp.zeros((slots,), jnp.int32)

    def submit(self, req: Request) -> None:
        req.queued_s = time.perf_counter()
        self.queue.append(req)

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.active)

    def _finished(self, req: Request) -> bool:
        """EOS-aware completion: a request retires when it emits its eos_id
        or exhausts its token budget, whichever comes first."""
        if req.eos_id is not None and req.out_tokens and \
                req.out_tokens[-1] == req.eos_id:
            return True
        return len(req.out_tokens) >= req.max_new_tokens

    def step(self) -> None:
        raise NotImplementedError

    def run(self, max_ticks: int = 10_000) -> None:
        ticks = 0
        while self.busy and ticks < max_ticks:
            self.step()
            ticks += 1


class ContinuousBatcher(_BatcherBase):
    """Fixed-slot continuous batching loop on one engine (dense cache)."""

    def __init__(self, engine: InferenceEngine, slots: int = 4):
        super().__init__(engine, slots)
        self.cache = engine.new_cache(slots)

    def _retire(self, i: int) -> None:
        self.active[i].done = True
        self.active[i] = None
        self.cache = _clear_lane(self.cache, i)

    def _fill_slots(self) -> None:
        admitted: List[int] = []
        tok_devs: List[jnp.ndarray] = []
        for i in range(self.slots):
            if self.active[i] is None and self.queue:
                req = self.queue.pop(0)
                req.admitted_s = time.perf_counter()
                self.active[i] = req
                # per-request prefill into a fresh single-lane cache, then
                # splice the lane into the batched cache
                lane_cache = self.engine.new_cache(1)
                batch = {"tokens": jnp.asarray(req.tokens, jnp.int32)[None]}
                logits, lane_cache = self.engine.prefill(batch, lane_cache)
                tok_devs.append(jnp.argmax(logits, axis=-1)[0]
                                .astype(jnp.int32))
                admitted.append(i)
                self.cache = _splice_lane(self.cache, lane_cache, i)
        if not admitted:
            return
        # seed next tick's decode input on device, then ONE batched host
        # sync for all admissions this tick (was one blocking int() each)
        tok_dev = jnp.stack(tok_devs)
        self._last_tok = self._last_tok.at[jnp.asarray(admitted)].set(tok_dev)
        toks = np.asarray(tok_dev)  # repro-lint: allow[jax-host-sync]
        for i, tok in zip(admitted, toks):
            req = self.active[i]
            req.out_tokens.append(int(tok))
            if self._finished(req):       # eos on the very first token
                self._retire(i)

    def step(self) -> None:
        """One scheduler tick: refill empty lanes, one batched decode step."""
        self._fill_slots()
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live:
            return
        logits, self.cache = self.engine.decode(self._last_tok[:, None], self.cache)
        # the argmax stays on device as next tick's input (dead lanes pick up
        # garbage — harmless, refill overwrites before any read); one host
        # sync per tick for the bookkeeping below
        tok_dev = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._last_tok = tok_dev
        toks = np.asarray(tok_dev)  # repro-lint: allow[jax-host-sync]
        for i in live:
            req = self.active[i]
            req.out_tokens.append(int(toks[i]))
            if self._finished(req):
                self._retire(i)


# ===========================================================================
# paged runtime
# ===========================================================================
class BlockAllocator:
    """Host-side refcounted free-list over the shared pool.

    Block 0 (``model.NULL_BLOCK``) is reserved as the garbage sink for
    redirected writes and is never handed out; usable capacity is
    ``num_blocks - 1``. Refcounts > 1 arise from prefix sharing — a block is
    returned to the free list only when its last reference drops.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is reserved)")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))   # pop() yields low ids
        self.refcount = [0] * num_blocks
        self.total_allocs = 0          # fresh blocks ever handed out
        self.peak_used = 0

    @property
    def total_blocks(self) -> int:
        return self.num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.total_blocks - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh blocks at refcount 1, or None if they don't fit."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self.refcount[b] = 1
        self.total_allocs += n
        self.peak_used = max(self.peak_used, self.used_blocks)
        return out

    def incref(self, blocks: List[int]) -> None:
        for b in blocks:
            if self.refcount[b] <= 0:
                raise ValueError(f"incref of free block {b}")
            self.refcount[b] += 1

    def decref(self, blocks: List[int]) -> None:
        for b in blocks:
            self.refcount[b] -= 1
            if self.refcount[b] < 0:
                raise ValueError(f"double free of block {b}")
            if self.refcount[b] == 0:
                self._free.append(b)


class PrefixBlockCache:
    """Content-addressed map of fully-written prompt blocks -> pool blocks.

    Keys chain parent-hash + the block's tokens, so a hit at depth d implies
    hits at all shallower depths (radix-tree semantics in a flat dict). Each
    entry holds one owned reference; ``evict`` releases entries whose only
    remaining reference is the cache's own, oldest first.
    """

    def __init__(self, allocator: BlockAllocator):
        self.allocator = allocator
        self._map: Dict[Tuple, int] = {}     # chain key -> block id
        self.hits = 0                        # blocks reused via sharing

    @staticmethod
    def _chain(prompt: np.ndarray, block_size: int, upto_blocks: int):
        key: Tuple = ()
        for b in range(upto_blocks):
            key = (key, tuple(int(t) for t in
                              prompt[b * block_size:(b + 1) * block_size]))
            yield key

    def match(self, prompt: np.ndarray, block_size: int) -> List[int]:
        """Longest shared prefix as a list of pool block ids. Matches at most
        ``(m - 1) // block_size`` blocks so every admitted request computes at
        least its final prompt token (whose logits seed decode)."""
        limit = (len(prompt) - 1) // block_size
        out: List[int] = []
        for key in self._chain(prompt, block_size, limit):
            blk = self._map.get(key)
            if blk is None:
                break
            out.append(blk)
        if out:
            self.allocator.incref(out)
            self.hits += len(out)
        return out

    def register(self, prompt: np.ndarray, block_size: int,
                 table: List[int], lo_block: int, hi_block: int) -> None:
        """Pin prompt blocks [lo_block, hi_block) — now fully written — under
        their content keys. Idempotent per key; the pin is an owned ref."""
        for b, key in enumerate(self._chain(prompt, block_size, hi_block)):
            if b < lo_block or key in self._map:
                continue
            self._map[key] = table[b]
            self.allocator.incref([table[b]])

    def evict(self, need: int) -> None:
        """Drop pinned-only entries (refcount == 1) until ``need`` blocks are
        free or nothing more can be released. Deepest chain entries go first:
        evicting a shallow key would orphan its descendants — ``match`` stops
        at the first miss, so they could never be reached again, yet would
        stay pinned."""
        if need <= self.allocator.free_blocks:
            return
        for key in reversed(list(self._map)):
            blk = self._map[key]
            if self.allocator.refcount[blk] == 1:
                del self._map[key]
                self.allocator.decref([blk])
                if self.allocator.free_blocks >= need:
                    return


@dataclass
class _LaneState:
    """Host-side bookkeeping for one decode lane of the paged batcher."""
    blocks: List[int]            # this request's block-table prefix (owned refs)
    prefilled: int               # prompt tokens already written (incl. shared)
    registered: int              # full prompt blocks already in the prefix map


class PagedContinuousBatcher(_BatcherBase):
    """Paged-KV continuous batching: block-table cache, chunked prefill
    interleaved with decode ticks, refcounted prefix sharing, and
    memory-aware admission.

    Interface-compatible with ``ContinuousBatcher`` (submit/step/run/busy)
    plus the observable memory state (``free_blocks``/``total_blocks``) the
    router exports to schedulers via ``PoolSnapshot``.
    """

    def __init__(self, engine: InferenceEngine, slots: int = 4, *,
                 num_blocks: int = 64, block_size: int = 16, chunk: int = 32,
                 prefix_sharing: bool = True):
        super().__init__(engine, slots)
        self.block_size = block_size
        self.chunk = chunk
        self.cache = engine.new_paged_cache(slots, num_blocks, block_size)
        self.allocator = BlockAllocator(num_blocks)
        self.prefix = PrefixBlockCache(self.allocator) if prefix_sharing else None
        self.max_blocks_per_lane = kv_blocks_needed(engine.max_len, block_size)
        self._lane: List[Optional[_LaneState]] = [None] * slots

    # ---------------------------------------------------------------- state
    @property
    def total_blocks(self) -> int:
        return self.allocator.total_blocks

    @property
    def free_blocks(self) -> int:
        """Admission headroom: free-list blocks plus what prefix eviction
        could reclaim (pinned-only entries)."""
        return self.allocator.free_blocks + self._evictable()

    def _evictable(self) -> int:
        if self.prefix is None:
            return 0
        return sum(1 for blk in self.prefix._map.values()
                   if self.allocator.refcount[blk] == 1)

    def submit(self, req: Request) -> None:
        need = self._blocks_needed(req)
        if need > min(self.max_blocks_per_lane, self.allocator.total_blocks):
            raise ValueError(
                f"request {req.rid}: worst-case context "
                f"{len(req.tokens) + req.max_new_tokens} tokens needs {need} "
                f"blocks, but a lane holds at most "
                f"{min(self.max_blocks_per_lane, self.allocator.total_blocks)}")
        super().submit(req)

    def _blocks_needed(self, req: Request) -> int:
        return kv_blocks_needed(len(req.tokens) + req.max_new_tokens,
                                self.block_size)

    # ------------------------------------------------------------ admission
    def _admit(self) -> None:
        """Memory-aware lane refill: FIFO head admitted only when its
        worst-case block need fits (after prefix reuse and eviction)."""
        for i in range(self.slots):
            if self.active[i] is not None or not self.queue:
                continue
            req = self.queue[0]
            prompt = np.asarray(req.tokens)
            need = self._blocks_needed(req)
            shared: List[int] = []
            if self.prefix is not None:
                shared = self.prefix.match(prompt, self.block_size)
            fresh_need = need - len(shared)
            if self.prefix is not None:
                self.prefix.evict(fresh_need)
            fresh = self.allocator.alloc(fresh_need)
            if fresh is None:                     # memory-bound: head waits
                if shared:
                    self.allocator.decref(shared)
                break
            self.queue.pop(0)
            req.admitted_s = time.perf_counter()
            self.active[i] = req
            blocks = shared + fresh
            self._lane[i] = _LaneState(blocks=blocks,
                                       prefilled=len(shared) * self.block_size,
                                       registered=len(shared))
            row = np.full((self.cache["block_tables"].shape[1],), NULL_BLOCK,
                          np.int32)
            row[:len(blocks)] = blocks
            self.cache = dict(
                self.cache,
                block_tables=self.cache["block_tables"].at[i].set(
                    jnp.asarray(row)),
                pos=self.cache["pos"].at[i].set(len(shared) * self.block_size))

    # ------------------------------------------------------------- prefill
    def _prefill_tick(self) -> None:
        """Advance every still-prefilling lane by one chunk. The final chunk
        yields the first output token, exactly like a dense prefill."""
        done_lanes: List[int] = []
        tok_devs: List[jnp.ndarray] = []
        for i in range(self.slots):
            req, lane = self.active[i], self._lane[i]
            if req is None or lane.prefilled >= len(req.tokens):
                continue
            prompt = np.asarray(req.tokens)
            m = len(prompt)
            c = min(self.chunk, m - lane.prefilled)
            with TraceAnnotation("batcher.prefill", rid=req.rid, tokens=c):
                buf = np.zeros((self.chunk,), np.int32)
                buf[:c] = prompt[lane.prefilled:lane.prefilled + c]
                logits, cache = self.engine.prefill_chunk(
                    jnp.asarray(buf)[None], self.cache, i, c)
                self.cache = self.engine.commit_paged(cache)
                lane.prefilled += c
                if self.prefix is not None:
                    full = min(lane.prefilled, m) // self.block_size
                    if full > lane.registered:
                        self.prefix.register(prompt, self.block_size,
                                             lane.blocks, lane.registered,
                                             full)
                        lane.registered = full
                if lane.prefilled >= m:
                    done_lanes.append(i)
                    tok_devs.append(jnp.argmax(logits, axis=-1)[0]
                                    .astype(jnp.int32))
        if not done_lanes:
            return
        # seed the decode input with a device-side scatter (the previous
        # device->host->device round trip stalled the tick), then ONE
        # batched host sync for all completions (was one blocking int()
        # per completing lane)
        with TraceAnnotation("batcher.prefill", lanes=len(done_lanes)):
            tok_dev = jnp.stack(tok_devs)
            self._last_tok = self._last_tok.at[jnp.asarray(done_lanes)].set(
                tok_dev)
        with TraceAnnotation("batcher.sync", phase="prefill"):
            toks = np.asarray(tok_dev)  # repro-lint: allow[jax-host-sync]
        for i, tok in zip(done_lanes, toks):
            req = self.active[i]
            req.out_tokens.append(int(tok))
            if self._finished(req):               # eos on the very first token
                self._retire(i)

    # -------------------------------------------------------------- decode
    def _decode_lanes(self) -> List[int]:
        """Lanes with complete prompts, excluding held ones: a held request
        has prefilled here but decodes elsewhere — its first token (seeded by
        the final prefill chunk) waits in ``out_tokens`` until ``adopt_lane``
        moves the KV to the decode pool."""
        return [i for i, r in enumerate(self.active)
                if r is not None and not r.hold
                and self._lane[i].prefilled >= len(r.tokens)]

    def _context_blocks(self, lanes: List[int]) -> int:
        """Blocks of the lanes' contexts as this tick's decode reads them
        (prompt and every token so far, the one being fed included): what
        the paged decode kernel walks a layer for those lanes."""
        return sum(kv_blocks_needed(len(self.active[i].tokens)
                                    + len(self.active[i].out_tokens),
                                    self.block_size) for i in lanes)

    def step(self) -> None:
        """One tick: admit, one prefill chunk per filling lane, one batched
        decode step for lanes with complete prompts. Decode lanes advance
        even while another lane's long prompt is mid-prefill."""
        with TraceAnnotation("batcher.step"):
            with TraceAnnotation("batcher.admit"):
                self._admit()
            self._prefill_tick()
            live = self._decode_lanes()
            if not live:
                return
            with TraceAnnotation("batcher.decode", lanes=len(live),
                                 blocks=self._context_blocks(live)):
                mask = np.zeros((self.slots,), bool)
                mask[live] = True
                logits, cache = self.engine.decode_paged(
                    self._last_tok[:, None], self.cache, jnp.asarray(mask))
                self.cache = self.engine.commit_paged(cache)
                # argmax stays on device as next tick's input; dead/prefilling
                # lanes pick up garbage, which is harmless — prefill
                # completion re-seeds them before any read. One host sync
                # per tick.
                tok_dev = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                self._last_tok = tok_dev
            with TraceAnnotation("batcher.sync", phase="decode"):
                toks = np.asarray(tok_dev)  # repro-lint: allow[jax-host-sync]
            for i in live:
                req = self.active[i]
                req.out_tokens.append(int(toks[i]))
                if self._finished(req):
                    self._retire(i)

    def _retire(self, i: int) -> None:
        self.active[i].done = True
        self.release_lane(i)

    def release_lane(self, i: int) -> None:
        """Free lane ``i`` without completing its request: drop the owned
        block refs (prefix-shared blocks stay pinned) and null the device
        row. ``_retire`` is release + done; a disaggregated handoff releases
        the prefill-side lane after ``adopt_lane`` copied its blocks out,
        leaving the request alive on the decode pool."""
        with TraceAnnotation("batcher.retire", rid=self.active[i].rid):
            lane = self._lane[i]
            self.active[i] = None
            self._lane[i] = None
            self.allocator.decref(lane.blocks)    # shared blocks stay pinned
            mb = self.cache["block_tables"].shape[1]
            self.cache = dict(
                self.cache,
                block_tables=self.cache["block_tables"].at[i].set(
                    jnp.full((mb,), NULL_BLOCK, jnp.int32)),
                pos=self.cache["pos"].at[i].set(0))

    # ------------------------------------------------------------- handoff
    def adopt_lane(self, req: Request, src: "PagedContinuousBatcher",
                   src_i: int) -> Optional[int]:
        """Resume a held request here: copy its prefilled KV blocks from
        ``src`` and seat it in a free decode lane.

        The request must have finished prefill on ``src`` (its first output
        token, seeded by the final prefill chunk, is in ``out_tokens``; the
        source lane's KV therefore holds exactly the ``m`` prompt tokens —
        the held lane never entered decode). Blocks are copied, not stolen:
        prefix-shared source blocks keep serving the source pool, and the
        caller releases the source lane afterwards (``src.release_lane``).

        Returns the KV payload bytes moved, or ``None`` when no free lane or
        not enough free blocks exist yet — the caller retries next tick, so
        a migration racing admission on a block-starved target degrades to
        waiting, never to a partial copy.
        """
        lane_src = src._lane[src_i]
        if src.active[src_i] is not req or not req.out_tokens or \
                lane_src.prefilled < len(req.tokens):
            raise ValueError(f"request {req.rid}: adopt_lane before its "
                             f"prefill completed on the source pool")
        if self.block_size != src.block_size:
            raise ValueError(
                f"KV migration needs equal block sizes "
                f"(src {src.block_size}, dst {self.block_size})")
        slot = next((i for i, r in enumerate(self.active) if r is None), None)
        if slot is None:
            return None
        ctx = len(req.tokens)                 # prompt only; see docstring
        need = self._blocks_needed(req)       # worst-case full-context hold
        if self.prefix is not None:
            self.prefix.evict(need)
        fresh = self.allocator.alloc(need)
        if fresh is None:                     # block-starved: retry next tick
            return None
        n_copy = kv_blocks_needed(ctx, self.block_size)
        self.cache, moved = migrate_kv_blocks(
            src.cache, lane_src.blocks[:n_copy], self.cache, fresh[:n_copy])
        self.active[slot] = req
        # migrated blocks are private copies — nothing registered for sharing
        self._lane[slot] = _LaneState(blocks=fresh, prefilled=ctx, registered=0)
        row = np.full((self.cache["block_tables"].shape[1],), NULL_BLOCK,
                      np.int32)
        row[:len(fresh)] = fresh
        self.cache = dict(
            self.cache,
            block_tables=self.cache["block_tables"].at[slot].set(
                jnp.asarray(row)),
            pos=self.cache["pos"].at[slot].set(ctx))
        self._last_tok = self._last_tok.at[slot].set(req.out_tokens[-1])
        req.hold = False
        return moved

    def stats(self) -> Dict[str, int]:
        return {
            "total_blocks": self.total_blocks,
            "free_blocks": self.allocator.free_blocks,
            "fresh_allocs": self.allocator.total_allocs,
            "peak_used": self.allocator.peak_used,
            "prefix_hits": self.prefix.hits if self.prefix else 0,
        }


# --------------------------------------------------------------------- lane ops
def migrate_kv_blocks(src_cache: Dict, src_blocks: List[int],
                      dst_cache: Dict, dst_blocks: List[int]) -> Tuple[Dict, int]:
    """Device-side KV-block migration between two paged pools.

    Gathers ``src_blocks`` along the pool axis (axis 1 of every
    ``(layers, num_blocks, Hkv, block_size, hd)`` pool tensor) from
    ``src_cache`` and scatters them into ``dst_blocks`` of ``dst_cache`` —
    the serving realisation of the bytes the pricing model charges via
    ``CostModel.migration_terms``. The source pool is read, never written
    (copy, not steal), so blocks shared through a ``PrefixBlockCache`` keep
    serving the source pool. Returns ``(new_dst_cache, payload_bytes)``
    where payload_bytes counts the K/V (+scale) bytes moved once.
    """
    if len(src_blocks) != len(dst_blocks):
        raise ValueError(f"block list length mismatch: {len(src_blocks)} "
                         f"source vs {len(dst_blocks)} destination")
    if not src_blocks:
        return dst_cache, 0
    src_ids = jnp.asarray(src_blocks, jnp.int32)
    dst_ids = jnp.asarray(dst_blocks, jnp.int32)
    out = dict(dst_cache)
    moved = 0
    # the block pools (``model.POOL_KEYS``) migrate; ``pos`` and
    # ``block_tables`` are per lane and stay host-managed
    for k in M.POOL_KEYS:
        if k not in src_cache:
            continue
        sv, dv = src_cache[k], dst_cache.get(k)
        if dv is None or sv.shape[:1] + sv.shape[2:] != dv.shape[:1] + dv.shape[2:] \
                or sv.dtype != dv.dtype:
            raise ValueError(
                f"pool geometry mismatch on {k!r}: migration needs the same "
                f"model/block_size/dtype on both ends")
        payload = sv[:, src_ids]
        out[k] = dv.at[:, dst_ids].set(payload)
        moved += payload.size * payload.dtype.itemsize
    return out, moved
# Cache keys whose leading axis is the batch (everything else produced by
# M.init_cache is layer-leading with batch at axis 1). Explicit metadata, not
# a shape heuristic: comparing v.shape[0] == lv.shape[0] misclassifies
# batch-leading tensors whenever slots == 1 (or slots == n_layers), silently
# corrupting the spliced cache.
_BATCH_LEADING_KEYS = frozenset({"pos"})


def _batch_axis(key: str, v) -> int:
    return 0 if key in _BATCH_LEADING_KEYS or v.ndim == 1 else 1


def _splice_lane(cache: Dict, lane: Dict, i: int) -> Dict:
    """Copy single-lane cache (batch dim 1) into batch position i."""
    out = dict(cache)
    for k, v in cache.items():
        lv = lane[k]
        if _batch_axis(k, v) == 0:
            out[k] = v.at[i].set(lv[0])
        else:
            out[k] = v.at[:, i].set(lv[:, 0])
    return out


def _clear_lane(cache: Dict, i: int) -> Dict:
    """Free a lane. Only ``pos`` needs resetting: the decode kernels mask by
    kv_len, so stale KV rows are unreachable; SSM states are overwritten by
    the next splice."""
    return dict(cache, pos=cache["pos"].at[i].set(0))
