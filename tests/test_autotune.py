"""Kernel autotuner and kernel dispatch: cache round-trip, env pinning,
dispatch parameters, parity.

  * the cache round-trips (write -> load -> resolve) and is deterministic at
    a fixed seed/env;
  * a cache recorded under a different environment fingerprint refuses to
    load (``StaleCacheError``);
  * with no kwarg, ops dispatch is bit-for-bit the kernels' own defaults,
    and an explicit kwarg reaches the kernel;
  * the models' int8 paged read (dequantize the layer, then the one paged
    decode kernel) is the old inline gather-dequantize composition, and
    matches the gather oracle within kernel tolerance;
  * the tuned winner is never slower than the default on the measured grid
    (the default is a candidate in every space);
  * ``TableOracle.from_autotune`` prices tuned timings within the fit's own
    error.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.pricing import (CalibratedOracle, KernelSample, TableOracle,
                                _predict, fit_calibration)
from repro.core.systems import SystemProfile
from repro.kernels import autotune as AT
from repro.kernels import decode_attention as DA
from repro.kernels import flash_attention as FA
from repro.kernels import ops, ref
from repro.kernels import ssm_scan as SS
from repro.launch import envcfg
from repro.models import attention as ATT

TOL = 3e-5          # kernel tolerance of the int8 paged read (float32)

HOST = SystemProfile(name="host-cpu", kind="eff", chips=1,
                     peak_flops=2.0e11, hbm_bw=5.0e10, ici_bw=0.0,
                     power_peak_w=65.0, power_idle_w=10.0, overhead_s=1e-3)


def det_timer(kernel, shape, *, params, backend, iters, seed):
    """Deterministic fake timer: time depends only on (kernel, shape,
    params), with a fixed non-default winner per kernel."""
    fast = {"flash_attention": {"block_q": 256},
            "ssm_scan": {"chunk": 64},
            "decode_attention": {"block_kv": 256}}
    base = 1e-3 * (1 + sum(shape.values()) / 1024)
    t = base * (0.5 if params == fast.get(kernel) else 1.0 + 0.01 * (
        sum(ord(c) for c in json.dumps(params, sort_keys=True)) % 7))
    return KernelSample(kernel, 1e9, 1e6,
                        float(shape.get("c", shape.get("s", 0))), t, 0.01)


def make_cache(backend="ref"):
    shapes = {"flash_attention": [{"s": 1024}], "ssm_scan": [{"s": 512}]}
    if backend != "ref":
        shapes["decode_attention"] = [{"b": 2, "c": 2048}]
    return AT.autotune(shapes, profile="host-cpu", backend=backend,
                       timer=det_timer)


# ------------------------------------------------------------- param spaces
def test_spaces_contain_defaults_first():
    for (kernel, backend), default in AT.DEFAULT_PARAMS.items():
        space = AT.param_space(kernel, backend)
        if not space:
            assert default == {}, (kernel, backend)
            continue
        assert space[0] == default, (kernel, backend)
        assert all(space.count(c) == 1 for c in space)


def test_shape_bucket_pow2():
    assert AT.shape_bucket("flash_attention", s=1024) == "s1024"
    assert AT.shape_bucket("flash_attention", s=1000) == "s1024"
    assert AT.shape_bucket("flash_attention", s=1025) == "s2048"
    assert AT.shape_bucket("decode_attention", b=6, c=1500) == "b8c2048"
    assert AT.shape_bucket("ssm_scan", s=512) == "s512"
    with pytest.raises(KeyError):
        AT.shape_bucket("nope", s=1)


# ------------------------------------------------------------------- cache
def test_cache_roundtrip(tmp_path):
    cache = make_cache()
    assert len(cache.entries) == 2
    path = cache.dump(AT.cache_path("host-cpu", "ref", str(tmp_path)))
    loaded = AT.AutotuneCache.load(path)
    assert loaded.to_json() == cache.to_json()
    for e in cache.entries.values():
        assert loaded.resolve(e.kernel, e.backend, e.bucket) == e.params
    assert loaded.resolve("flash_attention", "ref", "s4096") is None
    assert loaded.resolve("flash_attention", "pallas", "s1024") is None


def test_cache_deterministic_at_fixed_seed_env():
    a, b = make_cache(), make_cache()
    assert a.to_json() == b.to_json()


def test_stale_env_refused(tmp_path):
    cache = make_cache()
    path = cache.dump(str(tmp_path / "c.json"))
    with open(path) as f:
        data = json.load(f)
    data["env"]["jax"] = "0.0.0-stale"
    data["env_digest"] = envcfg.fingerprint_digest(data["env"])
    stale = str(tmp_path / "stale.json")
    with open(stale, "w") as f:
        json.dump(data, f)
    with pytest.raises(AT.StaleCacheError):
        AT.AutotuneCache.load(stale)
    # escape hatch for offline inspection
    assert AT.AutotuneCache.load(stale, require_env=False).entries
    # a tampered digest is corruption, not staleness
    data["env_digest"] = "0" * 16
    with open(stale, "w") as f:
        json.dump(data, f)
    with pytest.raises(ValueError, match="corrupt"):
        AT.AutotuneCache.load(stale, require_env=False)


def test_cache_version_pinned(tmp_path):
    cache = make_cache()
    data = cache.to_json()
    data["version"] = AT.CACHE_VERSION + 1
    path = str(tmp_path / "v.json")
    with open(path, "w") as f:
        json.dump(data, f)
    with pytest.raises(ValueError, match="version"):
        AT.AutotuneCache.load(path)


def test_env_fingerprint_tracks_captured_vars(monkeypatch):
    base = envcfg.fingerprint_digest()
    monkeypatch.setenv("REPRO_CACHE_MODE", "weird-test-value")
    assert envcfg.fingerprint_digest() != base


# --------------------------------------------- default parity (no kwarg)
def _attn_inputs(seed=0, B=2, Hq=4, Hkv=2, S=256, Dh=64):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Hq, S, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, Dh)), jnp.float32)
    return q, k, v


def _quant_inputs(seed=0, B=2, Hq=4, Hkv=2, Dh=64, bs=16, mb=8):
    rng = np.random.default_rng(seed)
    ctx = bs * mb
    nb = 1 + B * mb
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, Dh)), jnp.float32)
    kp = jnp.asarray(rng.integers(-127, 128, (nb, Hkv, bs, Dh)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (nb, Hkv, bs, Dh)), jnp.int8)
    ks = jnp.asarray(rng.uniform(.005, .02, (nb, Hkv, bs, 1)), jnp.float32)
    vs = jnp.asarray(rng.uniform(.005, .02, (nb, Hkv, bs, 1)), jnp.float32)
    tables = jnp.asarray(np.arange(1, 1 + B * mb).reshape(B, mb), jnp.int32)
    kv_len = jnp.asarray([ctx - (37 * i) % 101 for i in range(B)], jnp.int32)
    return q, kp, vp, ks, vs, tables, kv_len


def test_untuned_dispatch_bit_for_bit_historical():
    """With no kwarg, every ops entry point equals the direct kernel call
    with its historical hard-coded parameters."""
    q, k, v = _attn_inputs(S=2048)       # > ref default block_q, so chunking runs
    got = ops.flash_attention(q, k, v, backend="ref")
    want = ref.mha_attention_chunked(q, k, v, causal=True, block_q=1024)
    assert (got == want).all()

    got = ops.flash_attention(q[:, :, :256], k, v, backend="pallas_interpret")
    want = FA.flash_attention(q[:, :, :256], k, v, causal=True,
                              block_q=128, block_k=128, interpret=True)
    assert (got == want).all()

    qd = q[:, :, :1]
    kv_len = jnp.asarray([2048, 1500], jnp.int32)
    got = ops.decode_attention(qd, k, v, kv_len, backend="pallas_interpret")
    want = DA.decode_attention(qd, k, v, kv_len, block_k=128, interpret=True)
    assert (got == want).all()

    rng = np.random.default_rng(3)
    B, H, S, P, N = 2, 4, 384, 64, 64
    x = jnp.asarray(rng.normal(size=(B, H, S, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(.001, .2, (B, H, S)), jnp.float32)
    A = jnp.asarray(-rng.uniform(.5, 4., (H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
    y, fin = ops.ssd_scan(x, dt, A, Bm, Cm, backend="ref")
    yw, finw = ref.ssd_scan_chunked(x, dt, A, Bm, Cm, chunk=128)
    assert (y == yw).all() and (fin == finw).all()


def _int8_read(q, kp, vp, ks, vs, tables, kv_len, *, softcap=None,
               window=None, backend):
    """The models' int8 paged read: one decode step of
    ``attention.paged_decode_self_attention`` over the int8 pools (one
    layer), with identity q and o projections and no position encoding, so
    it returns the attention of ``q`` itself. The step's new rows, from
    random K/V projections, are written at each lane's ``kv_len - 1`` of
    the pools it returns, as ``commit_paged`` writes them.
    Returns (out, those (K, V, K scale, V scale) layer pools)."""
    B, Hq, _, D = q.shape
    Hkv, bs = kp.shape[1], kp.shape[2]
    cfg = dataclasses.replace(get_config("smollm-360m"), d_model=Hq * D,
                              num_heads=Hq, num_kv_heads=Hkv, head_dim=D,
                              pos_emb="none", qkv_bias=False,
                              attn_logit_softcap=softcap)
    params = ATT.attn_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    eye = {"w": jnp.eye(Hq * D, dtype=jnp.float32)}
    params = dict(params, q=eye, o=eye)
    x = q.transpose(0, 2, 1, 3).reshape(B, 1, Hq * D)
    at = kv_len - 1
    block_ids, rows = tables[jnp.arange(B), at // bs], at % bs
    out, new = ATT.paged_decode_self_attention(
        params, cfg, x, positions=at[:, None],
        pools=tuple(p[None] for p in (kp, vp, ks, vs)), layer=jnp.int32(0),
        block_tables=tables, kv_len=kv_len, window=window, backend=backend)
    staged = [ATT.write_rows(p, r, block_ids, rows)
              for p, r in zip((kp, vp, ks, vs), new)]
    return out.reshape(B, 1, Hq, D).transpose(0, 2, 1, 3), staged


def test_untuned_quant_path_is_old_inline_composition():
    """The models' int8 paged read, through the one paged decode kernel,
    is the gather + dequantize + dense-decode composition models.attention
    used to inline: bit-for-bit on ref, within kernel tolerance on the
    Pallas kernel in interpret mode."""
    q, kp, vp, ks, vs, tables, kv_len = _quant_inputs()
    for backend in ("ref", "pallas_interpret"):
        got, (k8, v8, k_scale, v_scale) = _int8_read(
            q, kp, vp, ks, vs, tables, kv_len, softcap=30.0, backend=backend)
        k_read = ref.dequantize_kv(ref.gather_paged_kv(k8, tables),
                                   ref.gather_paged_kv(k_scale, tables),
                                   q.dtype)
        v_read = ref.dequantize_kv(ref.gather_paged_kv(v8, tables),
                                   ref.gather_paged_kv(v_scale, tables),
                                   q.dtype)
        want = ops.decode_attention(q, k_read, v_read, kv_len, softcap=30.0,
                                    backend=backend)
        if backend == "ref":
            assert (got == want).all()
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=TOL)


# --------------------------------------------------------- tuned dispatch
def test_tuned_params_reach_kernels_and_kwargs_override(monkeypatch):
    """A tile passed to an ops entry point reaches the kernel; with none,
    nothing is passed and the kernel keeps its own default."""
    seen = {}
    orig_fa, orig_da, orig_ss = (FA.flash_attention, DA.decode_attention,
                                 SS.ssd_scan)

    def spy_fa(q, k, v, **kw):
        seen["flash"] = kw
        return orig_fa(q, k, v, **kw)

    monkeypatch.setattr(ops._fa, "flash_attention", spy_fa)
    q, k, v = _attn_inputs(S=1024)
    ops.flash_attention(q, k, v, backend="pallas_interpret", block_q=64)
    assert seen["flash"]["block_q"] == 64
    ops.flash_attention(q, k, v, backend="pallas_interpret")
    assert "block_q" not in seen["flash"]

    def spy_da(q, kc, vc, kv_len, **kw):
        seen["decode"] = kw
        return orig_da(q, kc, vc, kv_len, **kw)

    monkeypatch.setattr(ops._da, "decode_attention", spy_da)
    qd = q[:, :, :1]
    kv_len = jnp.full((2,), 1024, jnp.int32)
    kc = jnp.zeros((2, 2, 2048, 64), jnp.float32)
    ops.decode_attention(qd, kc, kc, kv_len, backend="pallas_interpret",
                         block_kv=256)
    assert seen["decode"]["block_k"] == 256
    ops.decode_attention(qd, kc, kc, kv_len, backend="pallas_interpret")
    assert "block_k" not in seen["decode"]

    # ssm: an explicit chunk overrides the default 128
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 2, 512, 16)), jnp.float32)
    dt = jnp.asarray(rng.uniform(.001, .2, (1, 2, 512)), jnp.float32)
    A = jnp.asarray(-rng.uniform(.5, 4., (2,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(1, 512, 8)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(1, 512, 8)), jnp.float32)

    def spy_ss(x, dt, A, Bm, Cm, **kw):
        seen["ssm"] = kw
        return orig_ss(x, dt, A, Bm, Cm, **kw)

    monkeypatch.setattr(ops._ss, "ssd_scan", spy_ss)
    ops.ssd_scan(x, dt, A, Bm, Cm, backend="pallas_interpret")
    assert seen["ssm"]["chunk"] == 128
    ops.ssd_scan(x, dt, A, Bm, Cm, chunk=32, backend="pallas_interpret")
    assert seen["ssm"]["chunk"] == 32


def test_autotune_never_slower_on_measured_grid():
    """Real (tiny) grid search on the interpreter backend: the recorded
    winner time can never exceed the recorded default time, because the
    default is a candidate in every space."""
    shapes = {"ssm_scan": [{"s": 64}], "flash_attention": [{"s": 64}]}
    cache = AT.autotune(shapes, profile="host-cpu",
                        backend="pallas_interpret", iters=2)
    assert len(cache.entries) == 2
    for e in cache.entries.values():
        assert e.t_s <= e.t_default_s
        assert e.speedup >= 1.0
    assert cache.geomean_speedup() >= 1.0


# --------------------------------------------------------- int8 paged read
@pytest.mark.parametrize("softcap,window", [(None, None), (30.0, None),
                                            (None, 48), (20.0, 48)])
def test_int8_fused_matches_gather_oracle(softcap, window):
    """The models' int8 paged read through the Pallas paged decode kernel
    (interpret mode) matches the gather oracle over the staged pools."""
    q, kp, vp, ks, vs, tables, kv_len = _quant_inputs(seed=7)
    got, staged = _int8_read(q, kp, vp, ks, vs, tables, kv_len,
                             softcap=softcap, window=window,
                             backend="pallas_interpret")
    want = ref.paged_decode_attention_quant(q, *staged, tables,
                                            kv_len=kv_len, softcap=softcap,
                                            window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_flash_block_validation():
    q, k, v = _attn_inputs(S=64)
    with pytest.raises(ValueError, match="power of two"):
        FA.flash_attention(q, k, v, block_q=96)


# ----------------------------------------------------------- oracle refresh
def test_table_oracle_from_autotune():
    cfg = get_config("deepseek-7b")
    # synthetic tuned samples from known roofline constants -> the refit
    # must price them back (and .calibration must expose the fit)
    rng = np.random.default_rng(0)
    truth_ce, truth_me, oh = 0.3, 0.5, 2e-4
    samples = []
    for i in range(12):
        # straddle the roofline knee so both efficiencies bind on some
        # samples (same harness as benchmarks/calibrate.py --synthetic)
        base = float(10.0 ** rng.uniform(-3.0, 0.0))
        r = float(rng.uniform(-1.5, 1.5))
        f = base * truth_ce * HOST.instance_peak_flops / (10.0 ** max(0.0, -r))
        b = base * truth_me * HOST.instance_hbm_bw / (10.0 ** max(0.0, r))
        t = oh + max(f / (HOST.instance_peak_flops * truth_ce),
                     b / (HOST.instance_hbm_bw * truth_me))
        samples.append(KernelSample("flash_attention", f, b, 0.0, t))
    oracle = TableOracle.from_autotune(cfg, HOST, samples, fit_sat_ctx=False)
    cal = oracle.calibration
    # grid-search precision floor is ~5% — same bound as the calibrate.py
    # synthetic recovery gate (0.08), not an exact-recovery claim
    assert cal is not None and cal.fit_rel_rmse < 0.08
    pred = _predict(samples, HOST, cal.compute_eff, cal.mem_eff, cal.sat_ctx,
                    cal.overhead_s)
    t = np.array([s.t_s for s in samples])
    assert np.all(np.abs(pred - t) / t < 0.10)
    # the grid was built eagerly and prices like its calibrated base
    base = CalibratedOracle([cal])
    for m, n in [(128, 64), (1024, 256), (777, 123)]:
        got = oracle.phases(cfg, m, n, HOST)
        want = base.phases(cfg, m, n, HOST)
        assert got.t_prefill == pytest.approx(want.t_prefill, rel=0.05)
        assert got.t_decode == pytest.approx(want.t_decode, rel=0.05)
    # an AutotuneCache is accepted directly (duck-typed tuned_samples())
    cache = make_cache()
    oracle2 = TableOracle.from_autotune(cfg, HOST, cache)
    assert oracle2.calibration.n_samples == len(cache.entries)


def test_fit_calibration_downweights_noisy_samples():
    """A wildly wrong sample flagged as noisy steers the fit less than the
    same sample claiming to be clean."""
    rng = np.random.default_rng(1)
    truth_ce, truth_me = 0.3, 0.5
    samples = []
    for i in range(10):
        f = 10.0 ** rng.uniform(9, 11)
        b = 10.0 ** rng.uniform(6, 8)
        t = max(f / (HOST.instance_peak_flops * truth_ce),
                b / (HOST.instance_hbm_bw * truth_me))
        samples.append(KernelSample("k", f, b, 0.0, t))
    bad = KernelSample("k", samples[0].flops, samples[0].bytes, 0.0,
                       samples[0].t_s * 3.0)

    def err(cal):
        pred = _predict(samples, HOST, cal.compute_eff, cal.mem_eff,
                        cal.sat_ctx, cal.overhead_s)
        t = np.array([s.t_s for s in samples])
        return float(np.sqrt(np.mean(((pred - t) / t) ** 2)))

    import dataclasses
    noisy = dataclasses.replace(bad, noise_frac=5.0)
    cal_clean_flag = fit_calibration(HOST, samples + [bad],
                                     fit_sat_ctx=False)
    cal_noisy_flag = fit_calibration(HOST, samples + [noisy],
                                     fit_sat_ctx=False)
    assert err(cal_noisy_flag) <= err(cal_clean_flag)
