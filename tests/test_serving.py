"""Serving integration: engine generation, continuous batching equivalence,
fleet routing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.systems import paper_fleet, tpu_fleet
from repro.models import model as M
from repro.serving.batching import ContinuousBatcher, Request
from repro.serving.engine import InferenceEngine
from repro.serving.router import FleetRouter

KEY = jax.random.PRNGKey(7)


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("smollm-360m").reduced()
    params = M.init_params(cfg, KEY)
    return InferenceEngine(cfg, params, max_len=96)


def test_generate_deterministic(engine):
    batch = {"tokens": jnp.arange(8, dtype=jnp.int32)[None]}
    a = engine.generate(batch, 6).tokens
    b = engine.generate(batch, 6).tokens
    np.testing.assert_array_equal(a, b)


def test_generate_batch_consistency(engine):
    """Each row of a batched generate equals its solo generate."""
    p1 = jnp.arange(8, dtype=jnp.int32)
    p2 = (jnp.arange(8, dtype=jnp.int32) * 3) % engine.cfg.vocab_size
    both = engine.generate({"tokens": jnp.stack([p1, p2])}, 5).tokens
    solo1 = engine.generate({"tokens": p1[None]}, 5).tokens
    solo2 = engine.generate({"tokens": p2[None]}, 5).tokens
    np.testing.assert_array_equal(both[0], solo1[0])
    np.testing.assert_array_equal(both[1], solo2[0])


def test_continuous_batching_matches_solo(engine):
    prompts = [np.arange(4 + i) % engine.cfg.vocab_size for i in range(5)]
    reqs = [Request(i, p, max_new_tokens=6) for i, p in enumerate(prompts)]
    cb = ContinuousBatcher(engine, slots=2)
    for r in reqs:
        cb.submit(r)
    cb.run()
    for r, p in zip(reqs, prompts):
        assert r.done
        solo = engine.generate({"tokens": jnp.asarray(p, jnp.int32)[None]}, 6)
        np.testing.assert_array_equal(np.asarray(r.out_tokens[:6]), solo.tokens[0])


def test_continuous_batching_single_slot_matches_solo(engine):
    """Regression for the _splice_lane shape heuristic: with slots=1 the old
    ``v.shape[0] == lv.shape[0]`` test misclassified batch-leading cache
    tensors and corrupted the spliced lane."""
    prompts = [np.arange(5 + 2 * i) % engine.cfg.vocab_size for i in range(3)]
    reqs = [Request(i, p, max_new_tokens=5) for i, p in enumerate(prompts)]
    cb = ContinuousBatcher(engine, slots=1)
    for r in reqs:
        cb.submit(r)
    cb.run()
    for r, p in zip(reqs, prompts):
        assert r.done
        solo = engine.generate({"tokens": jnp.asarray(p, jnp.int32)[None]}, 5)
        np.testing.assert_array_equal(np.asarray(r.out_tokens[:5]), solo.tokens[0])


def test_splice_lane_batch_leading_tensor_at_single_slot():
    """Unit regression: a 2-D batch-leading cache entry spliced at slots=1
    must receive the lane's row, not a layer-axis write."""
    from repro.serving.batching import _splice_lane
    cache = {"pos": jnp.zeros((1,), jnp.int32),
             "k": jnp.zeros((3, 1, 2, 4, 5)),          # layer-leading
             "last_tok": jnp.zeros((1, 7), jnp.int32)}  # batch-leading 2-D
    lane = {"pos": jnp.array([9], jnp.int32),
            "k": jnp.ones((3, 1, 2, 4, 5)),
            "last_tok": jnp.full((1, 7), 5, jnp.int32)}
    import repro.serving.batching as B
    old = B._BATCH_LEADING_KEYS
    B._BATCH_LEADING_KEYS = old | {"last_tok"}
    try:
        out = _splice_lane(cache, lane, 0)
    finally:
        B._BATCH_LEADING_KEYS = old
    assert int(out["pos"][0]) == 9
    np.testing.assert_array_equal(np.asarray(out["k"]), np.ones((3, 1, 2, 4, 5)))
    np.testing.assert_array_equal(np.asarray(out["last_tok"][0]), np.full(7, 5))


def test_continuous_batching_kv_quant_lane_ops():
    """_splice_lane/_clear_lane must carry the int8 cache's scale tensors:
    batched generation over a kv_quant cache matches the solo quant engine."""
    cfg = get_config("smollm-360m").reduced()
    params = M.init_params(cfg, KEY)
    qeng = InferenceEngine(cfg, params, max_len=96, kv_quant=True)
    assert qeng.new_cache(2)["k"].dtype.name == "int8"
    prompts = [np.arange(5 + 2 * i) % cfg.vocab_size for i in range(3)]
    reqs = [Request(i, p, max_new_tokens=5) for i, p in enumerate(prompts)]
    cb = ContinuousBatcher(qeng, slots=2)
    for r in reqs:
        cb.submit(r)
    cb.run()
    for r, p in zip(reqs, prompts):
        assert r.done
        solo = qeng.generate({"tokens": jnp.asarray(p, jnp.int32)[None]}, 5)
        np.testing.assert_array_equal(np.asarray(r.out_tokens[:5]),
                                      solo.tokens[0])


def test_continuous_batching_hybrid_family_lane_ops():
    """Hybrid cache family (ak/av shared-attention KV + conv/SSM state):
    splice/clear must handle every tensor, slots=1 included."""
    cfg = get_config("zamba2-1.2b").reduced()
    params = M.init_params(cfg, KEY)
    eng = InferenceEngine(cfg, params, max_len=96)
    cache = eng.new_cache(1)
    assert "ak" in cache and "ssm" in cache   # the families under test
    prompts = [np.arange(6 + 3 * i) % cfg.vocab_size for i in range(3)]
    for slots in (1, 2):
        reqs = [Request(i, p, max_new_tokens=4) for i, p in enumerate(prompts)]
        cb = ContinuousBatcher(eng, slots=slots)
        for r in reqs:
            cb.submit(r)
        cb.run()
        for r, p in zip(reqs, prompts):
            assert r.done
            solo = eng.generate({"tokens": jnp.asarray(p, jnp.int32)[None]}, 4)
            np.testing.assert_array_equal(np.asarray(r.out_tokens[:4]),
                                          solo.tokens[0])


def test_batcher_eos_terminates_early(engine):
    """EOS-aware completion: find the token the model actually emits first,
    declare it EOS, and check the request retires before max_new_tokens."""
    prompt = np.arange(8) % engine.cfg.vocab_size
    free = engine.generate({"tokens": jnp.asarray(prompt, jnp.int32)[None]}, 8)
    eos = int(free.tokens[0][2])          # token emitted at step 2
    req = Request(0, prompt, max_new_tokens=8, eos_id=eos)
    cb = ContinuousBatcher(engine, slots=2)
    cb.submit(req)
    cb.run()
    assert req.done
    assert len(req.out_tokens) <= 3       # stopped at the eos emission
    assert req.out_tokens[-1] == eos


def test_engine_sampled_generation_default_key(engine):
    """temperature>0 with key=None must not crash (seeded default key) and
    must be reproducible."""
    batch = {"tokens": jnp.arange(8, dtype=jnp.int32)[None]}
    a = engine.generate(batch, 5, temperature=0.8).tokens
    b = engine.generate(batch, 5, temperature=0.8).tokens
    np.testing.assert_array_equal(a, b)
    c = engine.generate(batch, 5, temperature=0.8,
                        key=jax.random.PRNGKey(123)).tokens
    assert a.shape == c.shape


def test_router_threshold_split(engine):
    eff, perf = paper_fleet()
    router = FleetRouter(engine.cfg, {"eff": eff, "perf": perf},
                         {"perf": engine, "eff": engine}, policy="threshold",
                         t_in=32)
    small = router.submit(np.arange(8), 4)
    large = router.submit(np.arange(64), 4)
    assert small.pool == "eff" and large.pool == "perf"
    assert small.energy_j > 0 and large.energy_j > 0
    rep = router.fleet_report()
    assert rep["eff"]["queries"] == 1 and rep["perf"]["queries"] == 1


def test_router_cost_optimal_prefers_cheaper_system(engine):
    eff, perf = tpu_fleet()
    router = FleetRouter(engine.cfg, {"eff": eff, "perf": perf},
                         policy="cost_optimal", lam=1.0)
    # tiny query: efficiency pool must win on energy
    assert router.route(4, 4) == "eff"


def test_router_batcher_backend_executes_and_reports(engine):
    """Routed execution through per-pool ContinuousBatchers: submit queues,
    drain() runs the decode loops, outputs match the solo engine."""
    eff, perf = paper_fleet()
    router = FleetRouter(engine.cfg, {"eff": eff, "perf": perf},
                         {"eff": engine, "perf": engine}, policy="threshold",
                         t_in=32)
    router.attach_batchers(slots=2)
    prompts = [np.arange(6) % engine.cfg.vocab_size,
               np.arange(64) % engine.cfg.vocab_size]
    routed = [router.submit(p, 4) for p in prompts]
    assert routed[0].pool == "eff" and routed[1].pool == "perf"
    assert all(rr.request is not None and not rr.request.done for rr in routed)
    router.drain()
    for rr, p in zip(routed, prompts):
        assert rr.request.done
        solo = engine.generate({"tokens": jnp.asarray(p, jnp.int32)[None]}, 4)
        np.testing.assert_array_equal(np.asarray(rr.request.out_tokens[:4]),
                                      solo.tokens[0])


def test_router_paged_batcher_backend(engine):
    """attach_batchers(paged=True): routed execution through the paged
    runtime matches solo generation, and the fleet snapshot exposes block
    occupancy to schedulers."""
    eff, perf = paper_fleet()
    router = FleetRouter(engine.cfg, {"eff": eff, "perf": perf},
                         {"eff": engine, "perf": engine}, policy="threshold",
                         t_in=32)
    router.attach_batchers(slots=2, paged=True, num_blocks=48, block_size=8,
                           chunk=8)
    prompts = [np.arange(6) % engine.cfg.vocab_size,
               np.arange(64) % engine.cfg.vocab_size]
    routed = [router.submit(p, 4) for p in prompts]
    router.batchers["eff"].step()                    # admit the small request
    snap = router._fleet_state().pools["eff"]
    assert snap.total_blocks == 47 and snap.block_size == 8
    assert snap.free_blocks < snap.total_blocks      # admission took blocks
    router.drain()
    for rr, p in zip(routed, prompts):
        assert rr.request.done
        solo = engine.generate({"tokens": jnp.asarray(p, jnp.int32)[None]}, 4)
        np.testing.assert_array_equal(np.asarray(rr.request.out_tokens[:4]),
                                      solo.tokens[0])


def test_router_accounting_reconciles_eos_engine_path(engine):
    """Satellite: energy/runtime booked at expected_n must be corrected to
    the actually emitted token count when EOS retires a request early."""
    eff, perf = paper_fleet()
    prompt = np.arange(8) % engine.cfg.vocab_size
    free = engine.generate({"tokens": jnp.asarray(prompt, jnp.int32)[None]}, 8)
    eos = int(free.tokens[0][2])          # emitted at step 2 -> stops early
    router = FleetRouter(engine.cfg, {"eff": eff, "perf": perf},
                         {"eff": engine, "perf": engine}, policy="threshold",
                         t_in=32)
    rr = router.submit(prompt, 8, eos_id=eos)
    st = router.fleet_report()[rr.pool]
    assert st["expected_tokens"] == len(prompt) + 8
    assert st["tokens"] < st["expected_tokens"]
    assert st["energy_j"] < st["expected_energy_j"]
    assert st["runtime_s"] < st["expected_runtime_s"]


def test_router_accounting_reconciles_eos_batcher_path(engine):
    eff, perf = paper_fleet()
    prompt = np.arange(8) % engine.cfg.vocab_size
    free = engine.generate({"tokens": jnp.asarray(prompt, jnp.int32)[None]}, 8)
    eos = int(free.tokens[0][2])
    router = FleetRouter(engine.cfg, {"eff": eff, "perf": perf},
                         {"eff": engine, "perf": engine}, policy="threshold",
                         t_in=32)
    router.attach_batchers(slots=2)
    router.submit(prompt, 8, eos_id=eos)
    before = dict(router.fleet_report()["eff"])
    router.drain()
    after = router.fleet_report()["eff"]
    assert before["energy_j"] == before["expected_energy_j"]  # pre-drain
    assert after["energy_j"] < after["expected_energy_j"]     # reconciled
    assert after["tokens"] < after["expected_tokens"]


def test_router_est_wait_sees_active_residents(engine):
    """Satellite: est_wait must include the residual decode of active lanes,
    not only queued requests — a pool mid-request with an empty queue is not
    free."""
    eff, perf = paper_fleet()
    router = FleetRouter(engine.cfg, {"eff": eff, "perf": perf},
                         {"eff": engine, "perf": engine}, policy="threshold",
                         t_in=32)
    router.attach_batchers(slots=2)
    idle = router._fleet_state().pools["eff"].est_wait_s
    assert idle == 0.0
    router.submit(np.arange(6) % engine.cfg.vocab_size, 32)
    cb = router.batchers["eff"]
    cb.step()                              # admit + first decode step
    assert not cb.queue and any(r is not None for r in cb.active)
    busy = router._fleet_state().pools["eff"].est_wait_s
    assert busy > 0.0                      # residual decode counted
    router.drain()


def test_router_capacity_aware_spills(engine):
    eff, perf = paper_fleet()
    router = FleetRouter(engine.cfg, {"eff": eff, "perf": perf},
                         policy="capacity_aware", lam=0.0,
                         counts={"m1-pro": 1, "swing-a100": 1})
    # lam=0 -> pure latency: a burst deep enough that the perf pool's queue
    # exceeds the eff pool's service time must spill to the eff pool
    pools = {router.route(8, 8, arrival_s=0.0) for _ in range(64)}
    assert len(pools) == 2


# ------------------------------------------------------------ served entry
def test_build_router_serves_paged_reduced():
    """The builder behind ``launch.serve`` and ``chip_smoke.py``, at the
    reduced size: a paged family gets paged batchers on both pools, and every
    request comes back done with its full token budget."""
    from repro.launch.serve import build_router
    from repro.serving.batching import PagedContinuousBatcher
    router = build_router("qwen2.5-3b", t_in=16, max_len=128, lanes=2)
    assert all(isinstance(cb, PagedContinuousBatcher)
               for cb in router.batchers.values())
    assert len(router.batchers) == 2
    cb = next(iter(router.batchers.values()))
    # every lane fits a full-length context at once
    assert cb.total_blocks == 2 * (128 // 16)
    rng = np.random.default_rng(0)
    routed = [router.submit(rng.integers(0, router.cfg.vocab_size, m), 5)
              for m in (9, 40, 12, 33)]
    router.drain()
    for res in routed:
        assert res.request.done and len(res.request.out_tokens) == 5
        assert all(0 <= t < router.cfg.vocab_size
                   for t in res.request.out_tokens)
    assert {res.pool for res in routed} == set(router.pools)


def test_chip_smoke_refuses_without_tpu():
    """On a host with no TPU the chip check exits non-zero before building
    any model, and never reports success."""
    import os
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout + proc.stderr
    assert "no TPU" in proc.stderr
    assert "build_s" not in proc.stdout          # failed before building


def test_compile_cache_dir(monkeypatch):
    """The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR
    says, else to one fixed directory inside the checkout."""
    import os
    from repro.launch.envcfg import compile_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache_dir() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
    first = compile_cache_dir()
    assert first == os.path.join(root, ".jax_cache")
    assert compile_cache_dir() == first


def test_use_compile_cache_writes_where_env_says(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper leaves it as JAX's
    cache directory and compiled programs land there."""
    import os
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(root, "src"))
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.envcfg import use_compile_cache\n"
            "print(use_compile_cache())\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir())
