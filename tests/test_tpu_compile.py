"""Compile the served path's kernels and steps for a TPU v5e chip.

Nothing here runs on a chip: each test compiles for one chip of a described
``v5e:2x2`` topology at qwen2.5-3b's published widths in bf16, at the shapes
``chip_smoke.py`` serves (8 lanes, 2048-token lanes, 16-token blocks), and
at mistral-7b's 16-layer stage as its benchmark cell serves it. The
chip's compiler refuses what interpret mode accepts (unaligned slices, too
much fast memory, programs that do not fit), so these guard every change to
the kernels or the paged steps.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.scheduler import kv_blocks_needed
from repro.kernels import decode_attention as DA
from repro.kernels import flash_attention as FA
from repro.models import model as M
from repro.serving.engine import InferenceEngine

CFG = get_config("qwen2.5-3b")
LANES, MAX_LEN, BLOCK_SIZE, CHUNK = 8, 2048, 16, 32
MAX_BLOCKS = kv_blocks_needed(MAX_LEN, BLOCK_SIZE)
NUM_BLOCKS = LANES * MAX_BLOCKS + 1
HQ, HKV, HD = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with the persistent compile
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _arr(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.fixture(scope="module")
def served_shapes(one_chip):
    """Params and one pool's paged cache, as shapes on the described chip."""
    params = jax.eval_shape(
        functools.partial(M.init_params, CFG, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: M.init_paged_cache(
        CFG, LANES, NUM_BLOCKS, BLOCK_SIZE, jnp.bfloat16,
        max_blocks_per_lane=MAX_BLOCKS))
    return _on(one_chip, params), _on(one_chip, cache)


def _kernel_args(kernel, s):
    bf16 = jnp.bfloat16
    q = _arr(s, (LANES, HQ, 1, HD), bf16)
    tables = _arr(s, (LANES, MAX_BLOCKS), jnp.int32)
    kv_len = _arr(s, (LANES,), jnp.int32)
    pool = _arr(s, (CFG.num_layers, NUM_BLOCKS, HKV, BLOCK_SIZE, HD), bf16)
    if kernel == "paged_decode_attention":
        rows = _arr(s, (LANES, HKV, HD), bf16)
        return DA.paged_decode_attention, (q, pool, pool, tables, kv_len,
                                           rows, rows,
                                           _arr(s, (), jnp.int32))
    if kernel == "decode_attention":
        cache = _arr(s, (LANES, HKV, MAX_LEN, HD), bf16)
        return DA.decode_attention, (q, cache, cache, kv_len)
    kv = _arr(s, (1, HKV, 1024, HD), bf16)
    return FA.flash_attention, (_arr(s, (1, HQ, 1024, HD), bf16), kv, kv)


@pytest.mark.parametrize("kernel", ["paged_decode_attention",
                                    "decode_attention", "flash_attention"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args = _kernel_args(kernel, one_chip)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert need <= HBM_BYTES, f"step needs {need} bytes of {HBM_BYTES}"
    return need


def test_decode_step_paged_compiles_for_v5e(one_chip, served_shapes):
    params, cache = served_shapes
    step = jax.jit(functools.partial(M.decode_step_paged, cfg=CFG,
                                     backend="pallas"))
    compiled = step.lower(params=params,
                          tokens=_arr(one_chip, (LANES, 1), jnp.int32),
                          cache=cache,
                          live=_arr(one_chip, (LANES,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_prefill_paged_chunk_compiles_for_v5e(one_chip, served_shapes):
    params, cache = served_shapes
    step = jax.jit(functools.partial(M.prefill_paged_chunk, cfg=CFG,
                                     backend="pallas"))
    scalar = _arr(one_chip, (), jnp.int32)
    compiled = step.lower(params=params,
                          tokens=_arr(one_chip, (1, CHUNK), jnp.int32),
                          cache=cache, lane=scalar, n_valid=scalar).compile()
    _fits(compiled)


def _served_step(step, engine, one_chip, params, cache):
    """The engine's own jitted paged step and the commit of its rows,
    lowered at the served shapes and compiled for the chip."""
    if step == "decode_step_paged":
        rows = LANES
        compiled = engine._decode_paged.lower(
            params=params, tokens=_arr(one_chip, (LANES, 1), jnp.int32),
            cache=cache, live=_arr(one_chip, (LANES,), jnp.bool_)).compile()
    else:
        rows = CHUNK
        scalar = _arr(one_chip, (), jnp.int32)
        compiled = engine._prefill_chunk.lower(
            params=params, tokens=_arr(one_chip, (1, CHUNK), jnp.int32),
            cache=cache, lane=scalar, n_valid=scalar).compile()
    new = {k: _arr(one_chip, (CFG.num_layers, rows, HKV, HD), jnp.bfloat16)
           for k in ("kp", "vp")}
    new["at"] = (_arr(one_chip, (rows,), jnp.int32),) * 2
    return compiled, engine._commit.lower(cache, new).compile()


def _pool_copies(compiled):
    layer = f"{NUM_BLOCKS},{HKV},{BLOCK_SIZE},{HD}"
    shape = rf"bf16\[(?:1,|{CFG.num_layers},)?{layer}\]"
    return re.findall(rf"%(\S*(?:copy|dynamic-update-slice)\S*) = {shape}",
                      compiled.as_text())


def _layer_values(compiled, cfg, num_blocks):
    """The instructions, fused or not, whose value is one layer's K/V pool
    or the whole-model pool, other than the pools as the step is given them
    and as its layer loop carries them (``parameter``,
    ``get-tuple-element``): each is a copy, a slice or a scatter of a
    layer."""
    layer = (f"{num_blocks},{cfg.num_kv_heads},{BLOCK_SIZE},"
             f"{cfg.resolved_head_dim}")
    found = re.findall(
        rf"%(\S+) = bf16\[(?:1,|{cfg.num_layers},)?{layer}\]\S* ([\w-]+)\(",
        compiled.as_text())
    return [f"{name} {op}" for name, op in found
            if op not in ("parameter", "get-tuple-element")]


@pytest.mark.parametrize("step", ["decode_step_paged", "prefill_paged_chunk"])
def test_paged_step_reads_its_pools_and_commit_writes_them_in_place(
        one_chip, served_shapes, step):
    """The step only reads the K/V pools: no pool comes out of it, and no
    value in it, fused or not, is a layer's pool or the whole pool: the
    decode kernel reads its layer straight from the whole-model pool in HBM
    and the step's new rows beside it, and prefill gathers its lane's
    blocks. The commit writes the step's rows into the donated pools in
    place, with no loop, so a trace never takes it for a paged step's layer
    loop."""
    params, cache = served_shapes
    engine = InferenceEngine(CFG, params, max_len=MAX_LEN, backend="pallas",
                             dtype=jnp.bfloat16)
    compiled, commit = _served_step(step, engine, one_chip, params, cache)
    layer_bytes = cache["kp"].size // CFG.num_layers * 2
    assert compiled.memory_analysis().output_size_in_bytes < layer_bytes
    assert not _pool_copies(compiled), _pool_copies(compiled)
    layer_values = _layer_values(compiled, CFG, NUM_BLOCKS)
    assert not layer_values, layer_values
    pools = cache["kp"].size * 2 + cache["vp"].size * 2
    assert commit.memory_analysis().alias_size_in_bytes >= pools
    assert not _pool_copies(commit), _pool_copies(commit)
    assert not re.search(r"%while\S* = ", commit.as_text())


def test_init_params_compiles_for_v5e(one_chip):
    """The jitted bf16 init the server builds its params with fits the chip:
    its float32 normals never all exist at once."""
    init = jax.jit(functools.partial(M.init_params, CFG, dtype=jnp.bfloat16))
    compiled = init.lower(jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=one_chip)).compile()
    _fits(compiled)


# mistral-7b's 16-layer stage as ``mistral_7b_16l.chat_backlog`` serves it:
# G = 4 query heads per KV head, 8 KV heads, a 4096-token window, 32 lanes
# of 1024 tokens a pool
MISTRAL = dataclasses.replace(get_config("mistral-7b"), num_layers=16)
M_LANES, M_MAX_LEN = 32, 1024
M_MAX_BLOCKS = kv_blocks_needed(M_MAX_LEN, BLOCK_SIZE)
M_NUM_BLOCKS = M_LANES * M_MAX_BLOCKS + 1


@pytest.mark.parametrize("step", ["paged_decode_attention", "decode_step_paged",
                                  "prefill_paged_chunk"])
def test_mistral_stage_compiles_and_fits_for_v5e(one_chip, step):
    """The kernel at the stage's shape, and each paged step over its weights
    and one pool, compile for the chip; a step's arguments and temporaries
    leave room on the chip for the other pool, and the decode step holds
    no value of a layer's pool (``_layer_values``)."""
    s, bf16, hd = one_chip, jnp.bfloat16, MISTRAL.resolved_head_dim
    cache = _on(s, jax.eval_shape(lambda: M.init_paged_cache(
        MISTRAL, M_LANES, M_NUM_BLOCKS, BLOCK_SIZE, bf16,
        max_blocks_per_lane=M_MAX_BLOCKS)))
    if step == "paged_decode_attention":
        rows = _arr(s, (M_LANES, MISTRAL.num_kv_heads, hd), bf16)
        compiled = DA.paged_decode_attention.lower(
            _arr(s, (M_LANES, MISTRAL.num_heads, 1, hd), bf16), cache["kp"],
            cache["vp"], cache["block_tables"], cache["pos"], rows, rows,
            _arr(s, (), jnp.int32), window=MISTRAL.sliding_window).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return
    params = _on(s, jax.eval_shape(
        functools.partial(M.init_params, MISTRAL, dtype=bf16),
        jax.random.PRNGKey(0)))
    engine = InferenceEngine(MISTRAL, params, max_len=M_MAX_LEN,
                             backend="pallas", dtype=bf16)
    if step == "decode_step_paged":
        compiled = engine._decode_paged.lower(
            params=params, tokens=_arr(s, (M_LANES, 1), jnp.int32),
            cache=cache, live=_arr(s, (M_LANES,), jnp.bool_)).compile()
        assert "tpu_custom_call" in compiled.as_text()
        layer_values = _layer_values(compiled, MISTRAL, M_NUM_BLOCKS)
        assert not layer_values, layer_values
    else:
        scalar = _arr(s, (), jnp.int32)
        compiled = engine._prefill_chunk.lower(
            params=params, tokens=_arr(s, (1, CHUNK), jnp.int32), cache=cache,
            lane=scalar, n_valid=scalar).compile()
    pool_bytes = 2 * cache["kp"].size * 2
    assert _fits(compiled) + pool_bytes <= HBM_BYTES


@pytest.mark.parametrize("model", ["qwen2.5-3b", "mistral_7b_16l"])
def test_decode_step_names_its_kernel_for_the_trace(one_chip, model):
    """The compiled decode step holds one Pallas call, the paged decode
    kernel, named after its jitted function: the chip benchmark's trace
    reader finds the kernel's executions by that name (``paged_decode``),
    so a renamed or split kernel would empty its metric."""
    s, bf16 = one_chip, jnp.bfloat16
    cfg, lanes, max_len = ((CFG, LANES, MAX_LEN) if model == "qwen2.5-3b"
                           else (MISTRAL, M_LANES, M_MAX_LEN))
    max_blocks = kv_blocks_needed(max_len, BLOCK_SIZE)
    cache = _on(s, jax.eval_shape(lambda: M.init_paged_cache(
        cfg, lanes, lanes * max_blocks + 1, BLOCK_SIZE, bf16,
        max_blocks_per_lane=max_blocks)))
    params = _on(s, jax.eval_shape(
        functools.partial(M.init_params, cfg, dtype=bf16),
        jax.random.PRNGKey(0)))
    engine = InferenceEngine(cfg, params, max_len=max_len, backend="pallas",
                             dtype=bf16)
    text = engine._decode_paged.lower(
        params=params, tokens=_arr(s, (lanes, 1), jnp.int32), cache=cache,
        live=_arr(s, (lanes,), jnp.bool_)).compile().as_text()
    calls = re.findall(r"%(\S+) = [^\n]* custom-call\([^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 1, calls
    assert re.fullmatch(r"paged_decode_attention\.\d+", calls[0]), calls


@pytest.mark.parametrize("model", ["qwen2.5-3b", "mistral_7b_16l"])
def test_int8_decode_step_compiles_and_fits_for_v5e(one_chip, model):
    """The decode step over int8 pools, which dequantizes each layer and
    reads it through the one paged decode kernel, compiles for the chip
    at each served configuration; its arguments and temporaries leave room
    on the chip for the other pool's int8 pools."""
    s, bf16 = one_chip, jnp.bfloat16
    cfg, lanes, max_len = ((CFG, LANES, MAX_LEN) if model == "qwen2.5-3b"
                           else (MISTRAL, M_LANES, M_MAX_LEN))
    max_blocks = kv_blocks_needed(max_len, BLOCK_SIZE)
    cache = _on(s, jax.eval_shape(lambda: M.init_paged_cache(
        cfg, lanes, lanes * max_blocks + 1, BLOCK_SIZE, bf16,
        max_blocks_per_lane=max_blocks, kv_quant=True)))
    assert cache["kp"].dtype == jnp.int8
    params = _on(s, jax.eval_shape(
        functools.partial(M.init_params, cfg, dtype=bf16),
        jax.random.PRNGKey(0)))
    engine = InferenceEngine(cfg, params, max_len=max_len, backend="pallas",
                             dtype=bf16, kv_quant=True)
    compiled = engine._decode_paged.lower(
        params=params, tokens=_arr(s, (lanes, 1), jnp.int32), cache=cache,
        live=_arr(s, (lanes,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    pool_bytes = sum(cache[k].size * cache[k].dtype.itemsize
                     for k in M.POOL_KEYS)
    assert _fits(compiled) + pool_bytes <= HBM_BYTES
