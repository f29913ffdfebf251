"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssd_scan

RNG = np.random.default_rng(42)


def _qkv(B, Hq, Hkv, Sq, Sk, D, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Hq, Sq, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Sk, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Sk, D)), dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# --------------------------------------------------------------- flash attention
@pytest.mark.parametrize("B,Hq,Hkv,S,D", [
    (1, 1, 1, 128, 64),     # MHA, exactly one block
    (2, 4, 2, 256, 64),     # GQA 2:1
    (1, 8, 1, 200, 128),    # MQA, ragged seq (padding path)
    (2, 6, 2, 384, 64),     # GQA 3:1
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, Hq, Hkv, S, D, dtype):
    q, k, v = _qkv(B, Hq, Hkv, S, S, D, dtype)
    want = ref.mha_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [1, 17, 64, 1000])
def test_flash_attention_sliding_window(window):
    q, k, v = _qkv(1, 4, 2, 300, 64, 64, jnp.float32)
    # note Sq=300 vs Sk=64? keep square for window semantics
    q, k, v = _qkv(1, 4, 2, 300, 300, 64, jnp.float32)
    want = ref.mha_attention(q, k, v, causal=True, window=window)
    got = flash_attention(q, k, v, causal=True, window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_softcap_and_noncausal():
    q, k, v = _qkv(2, 4, 4, 160, 160, 64, jnp.float32)
    for kwargs in ({"softcap": 30.0, "causal": True},
                   {"causal": False},
                   {"causal": False, "softcap": 10.0}):
        want = ref.mha_attention(q, k, v, **kwargs)
        got = flash_attention(q, k, v, interpret=True, **kwargs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_q_offset():
    """Chunked prefill: later q chunk with offset against full K."""
    q, k, v = _qkv(1, 2, 2, 64, 256, 64, jnp.float32)
    want = ref.mha_attention(q, k, v, causal=True, q_offset=192)
    got = flash_attention(q, k, v, causal=True, q_offset=192, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_chunked_ref_matches_dense_ref():
    q, k, v = _qkv(1, 4, 2, 1000, 1000, 64, jnp.float32)
    want = ref.mha_attention(q, k, v, causal=True, window=123)
    got = ref.mha_attention_chunked(q, k, v, causal=True, window=123, block_q=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# --------------------------------------------------------------- decode attention
@pytest.mark.parametrize("B,Hq,Hkv,Smax,D", [
    (1, 4, 4, 128, 64),
    (2, 8, 2, 300, 64),
    (3, 4, 1, 257, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, Hq, Hkv, Smax, D, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Hq, 1, D)), dtype)
    kc = jnp.asarray(RNG.normal(size=(B, Hkv, Smax, D)), dtype)
    vc = jnp.asarray(RNG.normal(size=(B, Hkv, Smax, D)), dtype)
    kv_len = jnp.asarray(RNG.integers(1, Smax + 1, size=(B,)), jnp.int32)
    want = ref.decode_attention(q, kc, vc, kv_len=kv_len)
    got = decode_attention(q, kc, vc, kv_len, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_decode_attention_window_and_softcap():
    B, Hq, Hkv, Smax, D = 2, 8, 2, 384, 64
    q = jnp.asarray(RNG.normal(size=(B, Hq, 1, D)), jnp.float32)
    kc = jnp.asarray(RNG.normal(size=(B, Hkv, Smax, D)), jnp.float32)
    vc = jnp.asarray(RNG.normal(size=(B, Hkv, Smax, D)), jnp.float32)
    kv_len = jnp.asarray([100, 384], jnp.int32)
    for kwargs in ({"window": 64}, {"softcap": 20.0}, {"window": 32, "softcap": 5.0}):
        want = ref.decode_attention(q, kc, vc, kv_len=kv_len, **kwargs)
        got = decode_attention(q, kc, vc, kv_len, interpret=True, **kwargs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


# ------------------------------------------------------- paged decode attention
# At the served block size (16, so a group of 8 blocks is 128 tokens) the
# lanes hold 0 (zeros out), 1, a block boundary, one past a group, and every
# block; "scattered" ids are distinct and shuffled, "shared" lanes also
# start with one prefix's blocks; entries past a context are the null
# block 0, as the batcher leaves them. "random" draws lengths and ids.
PAGED_LENS = [0, 1, 16, 129, 192]


def _paged_case(ids, B, bs, nb, mb):
    if ids == "random":
        return (RNG.integers(0, nb, size=(B, mb)),
                RNG.integers(1, mb * bs + 1, size=(B,)))
    kv_len = np.asarray(PAGED_LENS)
    tables = (1 + RNG.permutation(nb - 1)[:B * mb]).reshape(B, mb)
    if ids == "shared":
        tables[:, :3] = tables[0, :3]
    tables[np.arange(mb)[None, :] * bs >= kv_len[:, None]] = 0
    return tables, kv_len


@pytest.mark.parametrize("B,Hq,Hkv,bs,nb,mb,D,ids,kw", [
    pytest.param(1, 4, 4, 128, 8, 4, 64, "random", {},    # MHA, kernel-sized
                 id="1-4-4-128-8-4-64"),
    pytest.param(2, 8, 2, 16, 24, 6, 64, "random", {},    # GQA, small blocks
                 id="2-8-2-16-24-6-64"),
    pytest.param(3, 4, 1, 32, 12, 5, 128, "random", {},   # MQA
                 id="3-4-1-32-12-5-128"),
    # qwen2.5-3b's G 8 over 2 KV heads and mistral-7b's G 4 over 8
    pytest.param(5, 16, 2, 16, 61, 12, 128, "scattered", {}, id="g8-scattered"),
    pytest.param(5, 16, 2, 16, 61, 12, 128, "shared", {}, id="g8-shared"),
    pytest.param(5, 16, 2, 16, 61, 12, 128, "scattered",
                 {"window": 40, "softcap": 20.0}, id="g8-window-softcap"),
    pytest.param(5, 32, 8, 16, 61, 12, 128, "scattered", {}, id="g4-scattered"),
    pytest.param(5, 32, 8, 16, 61, 12, 128, "shared", {}, id="g4-shared"),
    pytest.param(5, 32, 8, 16, 61, 12, 128, "shared",
                 {"window": 100, "softcap": 5.0}, id="g4-window-softcap"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_matches_gathered_ref(B, Hq, Hkv, bs, nb, mb,
                                                     D, ids, kw, dtype):
    from repro.kernels.decode_attention import paged_decode_attention
    q = jnp.asarray(RNG.normal(size=(B, Hq, 1, D)), dtype)
    kp = jnp.asarray(RNG.normal(size=(nb, Hkv, bs, D)), dtype)
    vp = jnp.asarray(RNG.normal(size=(nb, Hkv, bs, D)), dtype)
    tables, kv_len = _paged_case(ids, B, bs, nb, mb)
    tables = jnp.asarray(tables, jnp.int32)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    want = ref.paged_decode_attention(q, kp, vp, tables, kv_len=kv_len, **kw)
    got = paged_decode_attention(q, kp, vp, tables, kv_len, interpret=True,
                                 **kw)
    live = np.asarray(kv_len) > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=TOL[dtype], rtol=TOL[dtype])
    assert not np.asarray(got, np.float32)[~live].any()    # empty lanes: 0
    # the gathered contiguous view reduces to dense decode exactly
    k = ref.gather_paged_kv(kp, tables)
    v = ref.gather_paged_kv(vp, tables)
    dense = ref.decode_attention(q, k, v, kv_len=kv_len, **kw)
    np.testing.assert_allclose(np.asarray(want, np.float32),
                               np.asarray(dense, np.float32), atol=0)


def test_paged_decode_attention_window_and_softcap():
    from repro.kernels.decode_attention import paged_decode_attention
    B, Hq, Hkv, bs, nb, mb, D = 2, 8, 2, 32, 16, 8, 64
    q = jnp.asarray(RNG.normal(size=(B, Hq, 1, D)), jnp.float32)
    kp = jnp.asarray(RNG.normal(size=(nb, Hkv, bs, D)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(nb, Hkv, bs, D)), jnp.float32)
    tables = jnp.asarray(RNG.integers(0, nb, size=(B, mb)), jnp.int32)
    kv_len = jnp.asarray([60, 256], jnp.int32)
    for kwargs in ({"window": 64}, {"softcap": 20.0},
                   {"window": 48, "softcap": 5.0}):
        want = ref.paged_decode_attention(q, kp, vp, tables, kv_len=kv_len,
                                          **kwargs)
        got = paged_decode_attention(q, kp, vp, tables, kv_len,
                                     interpret=True, **kwargs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------- ssd scan
@pytest.mark.parametrize("B,H,S,P,N,chunk", [
    (1, 1, 64, 32, 16, 32),
    (2, 3, 200, 32, 64, 64),     # ragged (padding path)
    (1, 4, 256, 64, 128, 128),   # full-size state
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan(B, H, S, P, N, chunk, dtype):
    x = jnp.asarray(RNG.normal(size=(B, H, S, P)), dtype)
    dt = jnp.asarray(RNG.uniform(0.001, 0.2, size=(B, H, S)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 4.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    y_want, fs_want = ref.ssd_scan(x, dt, A, Bm, Cm)
    y_got, fs_got = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_got, np.float32),
                               np.asarray(y_want, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(fs_got), np.asarray(fs_want),
                               atol=tol, rtol=tol)


def test_ssd_chunked_jnp_matches_sequential():
    B, H, S, P, N = 2, 2, 330, 32, 16
    x = jnp.asarray(RNG.normal(size=(B, H, S, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.001, 0.2, size=(B, H, S)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 4.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    y1, f1 = ref.ssd_scan(x, dt, A, Bm, Cm)
    y2, f2 = ref.ssd_scan_chunked(x, dt, A, Bm, Cm, chunk=128)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=2e-5)


def test_ssd_decode_step_matches_scan_tail():
    """Running decode steps from the scan's final state continues the sequence."""
    B, H, S, P, N = 1, 2, 96, 32, 16
    x = jnp.asarray(RNG.normal(size=(B, H, S + 3, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, H, S + 3)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S + 3, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S + 3, N)), jnp.float32)
    y_full, _ = ref.ssd_scan(x, dt, A, Bm, Cm)
    _, state = ref.ssd_scan(x[:, :, :S], dt[:, :, :S], A, Bm[:, :S], Cm[:, :S])
    for t in range(3):
        y_t, state = ref.ssd_decode_step(state, x[:, :, S + t], dt[:, :, S + t],
                                         A, Bm[:, S + t], Cm[:, S + t])
        np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_full[:, :, S + t]),
                                   atol=2e-5)
