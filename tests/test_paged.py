"""Paged-KV serving runtime: block-table cache correctness, dense/paged
parity, chunked prefill, prefix sharing, and memory-aware admission."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving.batching import (BlockAllocator, ContinuousBatcher,
                                    PagedContinuousBatcher, PrefixBlockCache,
                                    Request)
from repro.serving.engine import InferenceEngine

KEY = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("smollm-360m").reduced()
    params = M.init_params(cfg, KEY)
    return InferenceEngine(cfg, params, max_len=96)


@pytest.fixture(scope="module")
def moe_engine():
    cfg = get_config("phi3.5-moe-42b-a6.6b").reduced()
    params = M.init_params(cfg, KEY)
    return InferenceEngine(cfg, params, max_len=96)


def _requests(cfg, n=5, budget=6):
    prompts = [np.arange(4 + 3 * i) % cfg.vocab_size for i in range(n)]
    return [Request(i, p, max_new_tokens=budget) for i, p in enumerate(prompts)]


# ------------------------------------------------------------------ unit level
def test_init_paged_cache_shapes_and_guards():
    cfg = get_config("smollm-360m").reduced()
    c = M.init_paged_cache(cfg, lanes=3, num_blocks=10, block_size=8,
                           max_blocks_per_lane=4)
    hd = cfg.resolved_head_dim
    assert c["kp"].shape == (cfg.num_layers, 10, cfg.num_kv_heads, 8, hd)
    assert c["vp"].shape == c["kp"].shape
    assert c["block_tables"].shape == (3, 4)
    assert int(c["block_tables"].max()) == M.NULL_BLOCK
    assert c["pos"].shape == (3,)
    cq = M.init_paged_cache(cfg, 2, 6, 8, kv_quant=True)
    assert cq["kp"].dtype == jnp.int8 and cq["kp_scale"].shape[-1] == 1
    with pytest.raises(ValueError):
        M.init_paged_cache(get_config("mamba2-130m").reduced(), 2, 6, 8)
    with pytest.raises(ValueError):
        M.init_paged_cache(cfg, 2, 1, 8)      # null block needs company


def test_block_allocator_refcounts():
    a = BlockAllocator(6)                      # 5 usable, block 0 reserved
    assert a.total_blocks == 5 and a.free_blocks == 5
    got = a.alloc(3)
    assert got is not None and M.NULL_BLOCK not in got
    assert a.free_blocks == 2 and a.used_blocks == 3
    assert a.alloc(3) is None                  # doesn't fit -> no side effects
    assert a.free_blocks == 2
    a.incref(got[:1])                          # shared block: 2 refs
    a.decref(got)                              # request retires
    assert a.free_blocks == 4                  # shared one still held
    a.decref(got[:1])
    assert a.free_blocks == 5
    with pytest.raises(ValueError):
        a.decref(got[:1])                      # double free


def test_prefix_cache_match_register_evict():
    a = BlockAllocator(10)
    pc = PrefixBlockCache(a)
    prompt = np.arange(20)
    blocks = a.alloc(3)
    # register the first two full 8-token blocks as written
    pc.register(prompt, 8, blocks, 0, 2)
    assert a.refcount[blocks[0]] == 2          # owner + cache pin
    hit = pc.match(prompt, 8)
    assert hit == blocks[:2]                   # longest chain, capped at (m-1)//bs
    a.decref(hit)
    # different prompt: no hit
    assert pc.match(np.arange(20) + 1, 8) == []
    # release the owner; eviction can now reclaim the pinned blocks
    a.decref(blocks)
    free_before = a.free_blocks
    pc.evict(a.free_blocks + 2)
    assert a.free_blocks == free_before + 2


def test_prefix_cache_evicts_deepest_first():
    """Eviction must drop the deepest chain entries first: releasing a
    shallow key would orphan its descendants (match stops at the first miss)
    while they stay pinned."""
    a = BlockAllocator(5)                      # 4 usable
    pc = PrefixBlockCache(a)
    prompt = np.arange(24)
    blocks = a.alloc(3)
    pc.register(prompt, 8, blocks, 0, 3)
    a.decref(blocks)                           # only cache pins remain
    pc.evict(a.free_blocks + 1)                # reclaim one block
    hit = pc.match(prompt, 8)                  # cap: (24-1)//8 = 2 blocks
    assert hit == blocks[:2]                   # shallow chain still usable
    a.decref(hit)


# -------------------------------------------------------------- parity (dense)
def _run_pair(engine, reqs_dense, reqs_paged, slots=2, **paged_kw):
    dense = ContinuousBatcher(engine, slots=slots)
    for r in reqs_dense:
        dense.submit(r)
    dense.run()
    paged = PagedContinuousBatcher(engine, slots=slots, **paged_kw)
    for r in reqs_paged:
        paged.submit(r)
    paged.run()
    return paged


def test_paged_matches_dense_budget_capped(engine):
    a = _requests(engine.cfg)
    b = _requests(engine.cfg)
    paged = _run_pair(engine, a, b, num_blocks=48, block_size=8, chunk=8)
    for ra, rb in zip(a, b):
        assert ra.done and rb.done
        assert ra.out_tokens == rb.out_tokens
    assert paged.allocator.free_blocks == paged.total_blocks - \
        paged._evictable()                     # only prefix pins outstanding


def test_paged_matches_dense_eos(engine):
    """EOS-aware retirement: same early stop on both runtimes, and the paged
    side releases the retired request's blocks."""
    prompt = np.arange(8) % engine.cfg.vocab_size
    free = engine.generate({"tokens": jnp.asarray(prompt, jnp.int32)[None]}, 8)
    eos = int(free.tokens[0][2])
    a = [Request(0, prompt, 8, eos_id=eos)]
    b = [Request(0, prompt, 8, eos_id=eos)]
    paged = _run_pair(engine, a, b, num_blocks=32, block_size=8, chunk=8)
    assert a[0].out_tokens == b[0].out_tokens
    assert len(b[0].out_tokens) <= 3
    st = paged.stats()
    assert st["free_blocks"] + paged._evictable() == st["total_blocks"]


def test_paged_matches_dense_moe_family(moe_engine):
    a = _requests(moe_engine.cfg, n=4, budget=5)
    b = _requests(moe_engine.cfg, n=4, budget=5)
    _run_pair(moe_engine, a, b, num_blocks=48, block_size=8, chunk=8)
    for ra, rb in zip(a, b):
        assert ra.done and rb.done
        assert ra.out_tokens == rb.out_tokens


def test_paged_single_slot(engine):
    """slots=1: the whole loop is sequential admission; parity must hold."""
    a = _requests(engine.cfg, n=3)
    b = _requests(engine.cfg, n=3)
    _run_pair(engine, a, b, slots=1, num_blocks=32, block_size=8, chunk=16)
    for ra, rb in zip(a, b):
        assert ra.out_tokens == rb.out_tokens


def test_paged_kv_quant_runtime(engine):
    """int8 paged pools: same machinery, quantized blocks + scale pools.
    Greedy tokens should usually agree with the f32 paged run."""
    qeng = InferenceEngine(engine.cfg, engine.params, max_len=96,
                           kv_quant=True)
    reqs32 = _requests(engine.cfg, n=3, budget=6)
    reqs8 = _requests(engine.cfg, n=3, budget=6)
    p32 = PagedContinuousBatcher(engine, slots=2, num_blocks=32, block_size=8,
                                 chunk=8)
    p8 = PagedContinuousBatcher(qeng, slots=2, num_blocks=32, block_size=8,
                                chunk=8)
    assert p8.cache["kp"].dtype == jnp.int8
    for r in reqs32:
        p32.submit(r)
    for r in reqs8:
        p8.submit(r)
    p32.run()
    p8.run()
    agree = sum(a == b for ra, rb in zip(reqs32, reqs8)
                for a, b in zip(ra.out_tokens, rb.out_tokens))
    total = sum(len(r.out_tokens) for r in reqs32)
    assert all(r.done for r in reqs8)
    assert agree >= total - 2, (agree, total)


def _served_tokens(batcher, reqs):
    for r in reqs:
        batcher.submit(r)
    batcher.run()
    assert all(r.done for r in reqs)
    return [r.out_tokens for r in reqs]


@pytest.mark.parametrize("kind", ["bf16", "kv_quant"])
def test_paged_parity_after_many_ticks_and_chunks(engine, kind):
    """The in-place pool update over ten decode ticks and prompts of one to
    five 8-token chunks, with lanes retired and reused: the paged tokens do
    not depend on the lane count or the chunk size, and match the dense
    batcher's. bf16 matches exactly. An int8 dense prefill attends over its
    prompt's unquantized rows where the paged prefill reads them back
    quantized, so later layers cache rows that differ by rounding: there
    the first token matches and a rare later greedy flip is allowed."""
    cfg = engine.cfg
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    eng = InferenceEngine(cfg, M.init_params(cfg, KEY, dtype), max_len=96,
                          dtype=dtype, kv_quant=kind == "kv_quant")

    def reqs():
        return [Request(i, (np.arange(5 + 7 * i) * 3 + i) % cfg.vocab_size,
                        10) for i in range(5)]

    paged = _served_tokens(PagedContinuousBatcher(
        eng, slots=2, num_blocks=48, block_size=8, chunk=8), reqs())
    assert paged == _served_tokens(PagedContinuousBatcher(
        eng, slots=1, num_blocks=48, block_size=8, chunk=32), reqs())
    dense = _served_tokens(ContinuousBatcher(eng, slots=2), reqs())
    if kind == "bf16":
        assert paged == dense
    else:
        assert [t[0] for t in paged] == [t[0] for t in dense]
        agree = sum(a == b for p, d in zip(paged, dense)
                    for a, b in zip(p, d))
        assert agree >= sum(map(len, dense)) - 2, (paged, dense)


@pytest.mark.parametrize("step", ["decode_paged", "prefill_chunk"])
def test_paged_steps_read_their_cache_and_commit_writes_in_place(engine,
                                                                 step):
    """A paged step only reads its cache: the cache passed in stays live
    and unchanged, and the step's rows wait in the cache it returns. The
    commit writes them into the pools at their blocks and rows, in place:
    its cache is donated, so its buffers are deleted once it returns (the
    CPU backend honours donation)."""
    cache = engine.new_paged_cache(2, 8, 8)
    cache = dict(cache, block_tables=cache["block_tables"].at[:, 0].set(
        jnp.array([3, 5], jnp.int32)))
    if step == "decode_paged":
        _, out = engine.decode_paged(jnp.array([[7], [9]], jnp.int32), cache,
                                     jnp.ones((2,), bool))
        blocks, rows = [3, 5], [0, 0]
    else:
        _, out = engine.prefill_chunk(jnp.arange(8, dtype=jnp.int32)[None],
                                      cache, 1, 3)
        blocks, rows = [5, 5, 5], [0, 1, 2]
    new = out[M.NEW_ROWS]["kp"]
    assert not cache["kp"].is_deleted() and out["kp"] is cache["kp"]
    assert not jnp.any(cache["kp"])
    done = engine.commit_paged(out)
    assert cache["kp"].is_deleted() and cache["vp"].is_deleted()
    assert M.NEW_ROWS not in done
    got = done["kp"][:, jnp.array(blocks), :, jnp.array(rows)]
    np.testing.assert_array_equal(got,
                                  new[:, :len(blocks)].transpose(1, 0, 2, 3))
    assert jnp.any(new[:, :len(blocks)])
    assert engine.commit_paged(done) is done


def _layered_pools(cfg, quant, layers=5, nb=12, bs=8):
    """Whole-model pools whose every layer holds different data."""
    ks = jax.random.split(jax.random.PRNGKey(layers), 4)
    shape = (layers, nb, cfg.num_kv_heads, bs, cfg.resolved_head_dim)
    if not quant:
        return tuple(jax.random.normal(k, shape) for k in ks[:2])
    return tuple(jax.random.randint(k, shape, -127, 128).astype(jnp.int8)
                 for k in ks[:2]) + tuple(
        jax.random.uniform(k, shape[:-1] + (1,), minval=0.001, maxval=0.02)
        for k in ks[2:])


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("layer", [0, 2, 4])
def test_paged_attention_reads_its_own_layer(engine, quant, layer):
    """Paged attention at layer ``layer`` of whole-model pools, every layer
    different, equals the same attention given that layer alone: decode
    through the Pallas kernel (interpret mode) and a sliding window,
    prefill through the gathered blocks, on float and int8 pools."""
    from repro.models import attention as ATT
    cfg = engine.cfg
    p = engine.params["layers"]["attn"]
    p = jax.tree.map(lambda a: a[layer], p)
    pools = _layered_pools(cfg, quant)
    alone = tuple(q[layer:layer + 1] for q in pools)
    table = jnp.array([[3, 7, 1, 0], [9, 2, 0, 0]], jnp.int32)
    kv_len = jnp.array([20, 11], jnp.int32)
    x = jax.random.normal(KEY, (2, 1, cfg.d_model))
    decode = dict(positions=(kv_len - 1)[:, None], block_tables=table,
                  kv_len=kv_len, window=6, backend="pallas_interpret")
    got = ATT.paged_decode_self_attention(p, cfg, x, pools=pools,
                                          layer=jnp.int32(layer), **decode)
    want = ATT.paged_decode_self_attention(p, cfg, x, pools=alone,
                                           layer=jnp.int32(0), **decode)
    jax.tree.map(np.testing.assert_array_equal, got, want)
    x = jax.random.normal(KEY, (1, 8, cfg.d_model))
    prefill = dict(positions=(13 + jnp.arange(8))[None], table=table[0],
                   kv_len=jnp.array([18]), q_offset=jnp.int32(13))
    got = ATT.paged_prefill_chunk_attention(p, cfg, x, pools=pools,
                                            layer=jnp.int32(layer), **prefill)
    want = ATT.paged_prefill_chunk_attention(p, cfg, x, pools=alone,
                                             layer=jnp.int32(0), **prefill)
    jax.tree.map(np.testing.assert_array_equal, got, want)


def _staged_int8_attention(params, cfg, x, *, positions, pools, layer,
                           block_tables, kv_len, window=None,
                           backend="auto"):
    """The int8 paged decode read as it was formulated before the kernel
    took the new rows: write them into a copy of the layer's int8 pools,
    then read the whole lane, dequantized, through the gather oracle."""
    from repro.kernels import ref
    from repro.models import attention as ATT
    hd = cfg.resolved_head_dim
    q = ATT._split_heads(M.L.linear(params["q"], x), cfg.num_heads, hd)
    k = ATT._split_heads(M.L.linear(params["k"], x), cfg.num_kv_heads, hd)
    v = ATT._split_heads(M.L.linear(params["v"], x), cfg.num_kv_heads, hd)
    q, k = ATT._position_encode(cfg, q, k, positions)
    new = ATT._pool_rows(pools, k[:, :, 0, :], v[:, :, 0, :])
    bs = pools[0].shape[3]
    B = x.shape[0]
    block_ids = block_tables[jnp.arange(B), (kv_len - 1) // bs]
    staged = [ATT.write_rows(ATT._layer(p, layer), r, block_ids,
                             (kv_len - 1) % bs) for p, r in zip(pools, new)]
    out = ref.paged_decode_attention_quant(
        q, *staged, block_tables, kv_len=kv_len, window=window,
        softcap=cfg.attn_logit_softcap)
    return M.L.linear(params["o"], ATT._merge_heads(out)), new


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_int8_decode_step_matches_staged_formulation(engine, backend,
                                                     monkeypatch):
    """An int8 decode step, which hands the kernel its dequantized layer
    and its rows quantized then dequantized, gives the logits and rows of
    the staged formulation (rows written into the int8 layer, then read
    back dequantized): bit for bit on ref, within the kernel's f32
    tolerance in interpret mode. Lanes hold 1, 8 (a new row closing a
    block), 9 (opening one) and 21 tokens of pools whose every layer
    differs."""
    from repro.models import attention as ATT
    cfg = engine.cfg
    cache = M.init_paged_cache(cfg, 4, 12, 8, kv_quant=True,
                               max_blocks_per_lane=3)
    pools = _layered_pools(cfg, True, layers=cfg.num_layers, nb=12)
    cache.update(zip(M.POOL_KEYS, pools))
    cache["block_tables"] = jnp.array(
        [[4, 0, 0], [7, 0, 0], [2, 9, 0], [11, 3, 5]], jnp.int32)
    cache["pos"] = jnp.array([0, 7, 8, 20], jnp.int32)
    tokens = jnp.array([[5], [17], [29], [41]], jnp.int32)
    got = M.decode_step_paged(engine.params, cfg, tokens, cache,
                              backend=backend)
    monkeypatch.setattr(ATT, "paged_decode_self_attention",
                        _staged_int8_attention)
    want = M.decode_step_paged(engine.params, cfg, tokens, cache,
                               backend=backend)
    tol = 0 if backend == "ref" else 1e-4
    jax.tree.map(functools.partial(np.testing.assert_allclose, atol=tol,
                                   rtol=tol), got, want)


# ----------------------------------------------------------- chunked prefill
def test_chunked_prefill_decode_advances_during_long_prompt(engine):
    """A long prompt prefilling chunk-by-chunk must not stall resident decode
    lanes: the short request keeps emitting tokens while the long one is
    still mid-prefill."""
    long_req = Request(0, np.arange(80) % engine.cfg.vocab_size, 4)
    short = Request(1, np.arange(5) % engine.cfg.vocab_size, 12)
    cb = PagedContinuousBatcher(engine, slots=2, num_blocks=64, block_size=8,
                                chunk=8)
    cb.submit(long_req)
    cb.submit(short)
    interleaved = []
    ticks = 0
    while cb.busy and ticks < 60:
        cb.step()
        ticks += 1
        lane0 = cb._lane[0]
        if lane0 is not None and lane0.prefilled < len(long_req.tokens):
            interleaved.append(len(short.out_tokens))
    assert long_req.done and short.done
    # decode progressed across ticks where the long prompt was mid-prefill
    assert interleaved and interleaved[-1] > interleaved[0]
    # and the outputs still match the solo engine
    solo = engine.generate(
        {"tokens": jnp.asarray(long_req.tokens, jnp.int32)[None]}, 4)
    np.testing.assert_array_equal(np.asarray(long_req.out_tokens[:4]),
                                  solo.tokens[0])


# ------------------------------------------------------------ prefix sharing
def test_prefix_sharing_reuses_blocks(engine):
    """n requests sharing a 24-token prefix: later arrivals map the donor's
    full blocks instead of allocating fresh ones, and outputs are unchanged."""
    cfg = engine.cfg
    pre = np.arange(24) % cfg.vocab_size
    reqs = [Request(i, np.concatenate([pre, np.array([i + 1, i + 2])])
                    % cfg.vocab_size, 5) for i in range(4)]
    cb = PagedContinuousBatcher(engine, slots=2, num_blocks=48, block_size=8,
                                chunk=8)
    for r in reqs:
        cb.submit(r)
    cb.run()
    st = cb.stats()
    no_share = sum(-(-(len(r.tokens) + r.max_new_tokens) // 8) for r in reqs)
    assert st["prefix_hits"] > 0
    assert st["fresh_allocs"] < no_share       # allocated < sum of contexts
    for r in reqs:
        solo = engine.generate({"tokens": jnp.asarray(r.tokens, jnp.int32)[None]}, 5)
        np.testing.assert_array_equal(np.asarray(r.out_tokens[:5]),
                                      solo.tokens[0])


def test_prefix_sharing_disabled_allocates_full(engine):
    cfg = engine.cfg
    pre = np.arange(24) % cfg.vocab_size
    reqs = [Request(i, np.concatenate([pre, np.array([i + 1])])
                    % cfg.vocab_size, 4) for i in range(3)]
    cb = PagedContinuousBatcher(engine, slots=1, num_blocks=48, block_size=8,
                                chunk=8, prefix_sharing=False)
    for r in reqs:
        cb.submit(r)
    cb.run()
    st = cb.stats()
    assert st["prefix_hits"] == 0
    assert st["fresh_allocs"] == sum(
        -(-(len(r.tokens) + r.max_new_tokens) // 8) for r in reqs)


# ------------------------------------------------------- memory-aware admission
def test_memory_bound_admission_caps_concurrency(engine):
    """KV memory smaller than slots x max_len: concurrency is bounded by
    blocks, not slots, and the queue still drains as blocks free up."""
    reqs = [Request(i, np.arange(16) % engine.cfg.vocab_size, 8)
            for i in range(6)]
    # each request needs ceil(24/8)=3 blocks; 7 usable blocks, 4 slots
    cb = PagedContinuousBatcher(engine, slots=4, num_blocks=8, block_size=8,
                                chunk=16, prefix_sharing=False)
    peak = 0
    for r in reqs:
        cb.submit(r)
    ticks = 0
    while cb.busy and ticks < 400:
        cb.step()
        peak = max(peak, sum(1 for r in cb.active if r is not None))
        ticks += 1
    assert all(r.done for r in reqs)
    assert peak <= 2                            # 3 blocks each, 7 usable
    assert cb.allocator.peak_used <= cb.total_blocks


def test_paged_submit_rejects_impossible_request(engine):
    cb = PagedContinuousBatcher(engine, slots=1, num_blocks=4, block_size=8)
    with pytest.raises(ValueError):
        cb.submit(Request(0, np.arange(40), 8))  # 6 blocks > 3 usable
