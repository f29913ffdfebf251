"""Chip smoke check: serve qwen2.5-3b at full width in bf16 on one TPU.

    python chip_smoke.py

Builds the server the way ``python -m repro.launch.serve --full-config``
does (a ``FleetRouter`` with paged continuous batchers over one
``InferenceEngine``), answers a few requests through ``submit``/``drain``,
and checks what comes out:

  * every request is done with its full token budget, all tokens in range,
    both pools served work, and a shared prompt prefix reused pool blocks;
  * the compiled paged decode step calls the Pallas kernel
    (``tpu_custom_call`` in its HLO);
  * one paged decode step on a live cache gives the same logits through the
    Pallas kernel as through the jnp reference, within ``LOGIT_TOL``, and a
    one-token-short context or a shifted block-table walk does not.

The lines printed on the way (compile and wall seconds, peak device bytes)
are readings of one smoke run, not benchmark metrics. The last line is one
JSON object with ``"ok": true`` and the device. Any failed check exits
non-zero. Without a TPU it fails at once, before building anything: there is
no CPU fallback.
"""
from __future__ import annotations

import functools
import importlib.metadata
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "qwen2.5-3b"
LANES = 8
MAX_LEN = 2048
N_REQUESTS = 10
NEW_TOKENS = 32
PROMPT_MIN, PROMPT_MAX = 64, 1024
# threshold policy: prompts at the clamp floor go to the efficiency pool,
# longer ones to the performance pool, so both pools serve work
T_IN = PROMPT_MIN
# prompt lengths of the live lanes in the numerics check, seated in two
# groups: long multi-block contexts first, then (once those are decoding) a
# single token and both sides of a block boundary, so that short contexts
# are still short when the step runs
CHECK_PROMPTS = ((1000, 511, 130, 33), (17, 16, 5, 1))
# max |pallas - ref| over max |ref|, per lane. Both paths read the same bf16
# cache with f32 accumulation and differ by bf16 rounding, which grows with
# depth: 0.005 at 2 layers and 0.011 at 8 (Pallas interpreter on the host,
# full width), 0.018 at 36 layers compiled on a TPU v5e. A kernel masking
# one key short, or walking the block table one block behind, moved these
# lanes' logits by 0.43 to 0.83 on the host. The in-run controls below must
# also land above the tolerance.
LOGIT_TOL = 0.1


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def report(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


def report_memory(dev, when: str) -> None:
    stats = dev.memory_stats()
    report(f"bytes_in_use {when}", stats["bytes_in_use"])
    report(f"peak_bytes_in_use {when}", stats["peak_bytes_in_use"])


def check_device():
    """The run needs a TPU and the compiled Pallas kernels: no fallback."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    return dev


def compile_steps(pool):
    """AOT-compile the engine's two paged steps at the run's shapes; returns
    the compiled decode step."""
    engine, cache = pool.engine, pool.cache
    tok = jnp.zeros((LANES, 1), jnp.int32)
    live = jnp.ones((LANES,), bool)
    t0 = time.perf_counter()
    decode = engine._decode_paged.lower(
        params=engine.params, tokens=tok, cache=cache, live=live).compile()
    report("compile_s decode_step_paged", time.perf_counter() - t0)
    t0 = time.perf_counter()
    engine._prefill_chunk.lower(
        params=engine.params, tokens=jnp.zeros((1, pool.chunk), jnp.int32),
        cache=cache, lane=0, n_valid=pool.chunk).compile()
    report("compile_s prefill_paged_chunk", time.perf_counter() - t0)
    return decode


def run_requests(router) -> list:
    """Submit the workload, drain, then submit one request sharing the
    first one's prompt prefix and drain again (its prefix blocks are
    registered by then)."""
    from repro.core.workload import sample_workload
    rng = np.random.default_rng(0)
    vocab = router.cfg.vocab_size
    prompts = [rng.integers(0, vocab, size=int(np.clip(q.m, PROMPT_MIN,
                                                       PROMPT_MAX)))
               for q in sample_workload(N_REQUESTS - 1, seed=0)]
    routed = [router.submit(p, NEW_TOKENS) for p in prompts]
    t0 = time.perf_counter()
    router.drain()
    report("drain_s", time.perf_counter() - t0)
    bs = next(iter(router.batchers.values())).block_size
    shared = np.concatenate([prompts[0][:3 * bs],
                             rng.integers(0, vocab, size=bs)])
    routed.append(router.submit(shared, NEW_TOKENS))
    t0 = time.perf_counter()
    router.drain()
    report("drain_s shared_prefix_request", time.perf_counter() - t0)
    return routed


def check_requests(router, routed) -> None:
    vocab = router.cfg.vocab_size
    for res in routed:
        out = np.asarray(res.request.out_tokens)
        if not res.request.done or len(out) != NEW_TOKENS:
            fail(f"request {res.rid} on {res.pool}: done={res.request.done}, "
                 f"{len(out)} of {NEW_TOKENS} tokens")
        if out.min() < 0 or out.max() >= vocab:
            fail(f"request {res.rid}: token outside [0, {vocab})")
    report("tokens_emitted", sum(len(r.request.out_tokens) for r in routed))
    for pool, st in router.fleet_report().items():
        report(f"requests {pool}", st["queries"])
        if st["queries"] == 0:
            fail(f"pool {pool} served no request (threshold t_in={T_IN})")
    hits = sum(cb.stats()["prefix_hits"] for cb in router.batchers.values())
    report("prefix_block_hits", hits)
    if hits == 0:
        fail("the shared-prefix request reused no pool block")


def rel_err(got, want) -> float:
    """Largest per-lane max |got - want| / max |want| over the lanes."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want).max(-1) / np.abs(want).max(-1)))


def check_numerics(router) -> None:
    """One paged decode step on live lanes of varied context, through the
    Pallas kernel and through the jnp reference."""
    from repro.models import model as M
    from repro.serving.batching import Request
    cb = next(iter(router.batchers.values()))
    engine = cb.engine
    rng = np.random.default_rng(1)
    seated = 0
    for group in CHECK_PROMPTS:
        for m in group:
            seated += 1
            cb.submit(Request(-seated, rng.integers(0, engine.cfg.vocab_size,
                                                    m),
                              max_new_tokens=4 * NEW_TOKENS))
        while len(cb._decode_lanes()) < seated:
            cb.step()
    live = jnp.ones((LANES,), bool)
    tok, cache = cb._last_tok[:, None], cb.cache
    got = engine.decode_paged(tok, cache, live)[0]
    ref_step = jax.jit(functools.partial(M.decode_step_paged, cfg=engine.cfg,
                                         backend="ref"))
    t0 = time.perf_counter()
    want = jax.block_until_ready(
        ref_step(params=engine.params, tokens=tok, cache=cache, live=live)[0])
    report("compile_and_run_s decode_step_paged_ref", time.perf_counter() - t0)
    err = rel_err(got, want)
    report("kv_len checked", np.asarray(cache["pos"] + 1).tolist())
    report("logit_err pallas_vs_ref", err)
    report("logit_tol", LOGIT_TOL)
    if not err <= LOGIT_TOL:
        fail(f"Pallas paged decode logits off the reference by {err} "
             f"(tolerance {LOGIT_TOL})")
    controls = {
        "one_token_short": dict(cache, pos=cache["pos"] - 1),
        "block_walk_shifted": dict(
            cache, block_tables=jnp.roll(cache["block_tables"], -1, axis=1)),
    }
    for name, bad in controls.items():
        c_err = rel_err(engine.decode_paged(tok, bad, live)[0], want)
        report(f"logit_err control {name}", c_err)
        if not c_err > LOGIT_TOL:
            fail(f"control {name} stays within the tolerance ({c_err}): "
                 f"the check could not see that fault")


def main() -> None:
    dev = check_device()
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.kernels import ops
    from repro.launch.envcfg import use_compile_cache
    from repro.launch.serve import build_router
    if ops.resolve_backend() != "pallas":
        fail(f"kernel dispatch resolves to {ops.resolve_backend()!r}, "
             f"not 'pallas'")
    report("device_kind", dev.device_kind)
    report("device_count", len(jax.devices()))
    for pkg in ("jax", "jaxlib", "libtpu"):
        report(f"version {pkg}", importlib.metadata.version(pkg))
    report("compile_cache_dir", use_compile_cache())

    t0 = time.perf_counter()
    router = build_router(ARCH, full_config=True, t_in=T_IN,
                          max_len=MAX_LEN, lanes=LANES)
    pool = next(iter(router.batchers.values()))
    engine, cfg = pool.engine, pool.engine.cfg
    jax.block_until_ready(engine.params)
    report("build_s", time.perf_counter() - t0)
    report("model", f"{cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
                    f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
                    f"vocab={cfg.vocab_size}")
    report("param_dtype", jax.tree.leaves(engine.params)[0].dtype)
    report("param_bytes", sum(x.nbytes for x in jax.tree.leaves(engine.params)))
    report("kv_pool_bytes per pool",
           pool.cache["kp"].nbytes + pool.cache["vp"].nbytes)
    report_memory(dev, "after build")
    decode = compile_steps(pool)
    report("decode_step_paged memory_analysis", decode.memory_analysis())

    routed = run_requests(router)
    report_memory(dev, "after drain")
    check_requests(router, routed)
    if "tpu_custom_call" not in decode.as_text():
        fail("no tpu_custom_call in the compiled paged decode step: the "
             "Pallas kernel is not on the path")
    report("tpu_custom_call in decode_step_paged", True)
    check_numerics(router)
    report_memory(dev, "at end")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
