"""Wall-clock microbenchmarks of the real JAX serving/training steps
(reduced configs — CPU container; TPU numbers come from the roofline), plus
``kernel_phase_samples``: timed invocations of the shipped attention/SSM
kernels with their analytic work counts attached — the measurement feed for
``core.pricing.fit_calibration`` / ``CalibratedOracle``."""
from __future__ import annotations

import functools
import statistics
import time
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.pricing import KernelSample
from repro.kernels import ops, ref
from repro.models import model as M
from repro.serving.engine import InferenceEngine
from repro.training import data as D
from repro.training import optimizer as OPT
from repro.training.train import make_train_step


def _time(fn, *args, iters: int = 5) -> Tuple[float, float]:
    """(best seconds, noise_frac) per call, compile + warmup excluded.

    Every timed repetition blocks on the result INSIDE its own timed region
    (async dispatch would otherwise attribute one call's device time to a
    later iteration). Best-of-k, not mean: shared-host scheduling noise is
    strictly additive, so the minimum is the best estimator of the kernel's
    own time. noise_frac = (median - best) / best is the spread the
    calibration fit uses to down-weight noisy samples.
    """
    for _ in range(2):
        jax.block_until_ready(fn(*args))  # compile + warmup
    reps = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        reps.append(time.perf_counter() - t0)
    best = min(reps)
    med = statistics.median(reps)
    return best, (med - best) / best if best > 0 else 0.0


def _time_s(fn, *args, iters: int = 5) -> float:
    """Best wall seconds per call (see ``_time``)."""
    return _time(fn, *args, iters=iters)[0]


def _bench(fn, *args, iters: int = 5):
    """Best-of-k microseconds per call (same hygiene as ``_time``)."""
    return _time(fn, *args, iters=iters)[0] * 1e6


# ------------------------------------------------------- calibration samples
def time_kernel(kernel: str, shape: Mapping[str, int], *,
                params: Optional[Mapping[str, object]] = None,
                backend: Optional[str] = None, iters: int = 5, seed: int = 0,
                heads: int = 4, kv_heads: int = 2, head_dim: int = 64,
                state_dim: int = 64, ssm_head_dim: int = 64,
                page_block: int = 16) -> KernelSample:
    """Time ONE kernel cell through ``kernels.ops`` dispatch.

    ``kernel`` is one of "flash_attention" (shape {"s", ["b"]}),
    "decode_attention" / "paged_decode_quant" (shape {"b", "c"}), or
    "ssm_scan" (shape {"s", ["b"]}). ``params`` are the tile kwargs to pin
    for this measurement (the autotuner's candidate grid; None = the
    kernels' defaults; "paged_decode_quant" has none). Returns a ``KernelSample`` carrying the cell's
    analytic work counts and the best-of-k time + noise — the unit the
    autotuner (``kernels.autotune``) and ``kernel_phase_samples`` are built
    on.
    """
    rng = np.random.default_rng(seed)
    bk = {"backend": backend} if backend else {}
    p: Dict[str, object] = dict(params) if params else {}
    isz = 4  # float32
    Hq, Hkv, Dh = heads, kv_heads, head_dim
    # The jnp stand-in path (non-TPU hosts) materializes the (Sq, Sk) score
    # matrix that the fused Pallas kernel keeps in VMEM — count those bytes
    # when that is the variant actually being timed, so the fit targets the
    # measured kernel, not an idealized one.
    materializes_scores = ops.resolve_backend(backend or "auto") == "ref"

    if kernel == "flash_attention":
        B, S = int(shape.get("b", 1)), int(shape["s"])
        fa = jax.jit(functools.partial(ops.flash_attention, causal=True,
                                       **p, **bk))
        q = jnp.asarray(rng.normal(size=(B, Hq, S, Dh)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, Hkv, S, Dh)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, Hkv, S, Dh)), jnp.float32)
        t, nf = _time(fa, q, k, v, iters=iters)
        if materializes_scores:
            # the jnp path computes the FULL unmasked S x S einsums and masks
            # afterward — no causal halving in executed FLOPs
            flops = 4.0 * B * Hq * S * S * Dh
        else:
            flops = 2.0 * B * Hq * S * S * Dh          # QK^T + PV, causal-halved
        byts = isz * (2.0 * B * Hq * S * Dh + 2.0 * B * Hkv * S * Dh)
        if materializes_scores:
            byts += isz * 3.0 * B * Hq * S * S         # scores: write, softmax, read
        return KernelSample("flash_attention", flops, byts, float(S), t, nf)

    if kernel == "decode_attention":
        B, ctx = int(shape["b"]), int(shape["c"])
        da = jax.jit(functools.partial(ops.decode_attention, **p, **bk))
        q = jnp.asarray(rng.normal(size=(B, Hq, 1, Dh)), jnp.float32)
        kc = jnp.asarray(rng.normal(size=(B, Hkv, ctx, Dh)), jnp.float32)
        vc = jnp.asarray(rng.normal(size=(B, Hkv, ctx, Dh)), jnp.float32)
        kv_len = jnp.full((B,), ctx, jnp.int32)
        t, nf = _time(da, q, kc, vc, kv_len, iters=iters)
        flops = 4.0 * B * Hq * ctx * Dh                # QK^T + PV at length ctx
        byts = isz * (2.0 * B * Hkv * ctx * Dh + 2.0 * B * Hq * Dh)
        if materializes_scores:
            byts += isz * 3.0 * B * Hq * ctx
        return KernelSample("decode_attention", flops, byts, float(ctx), t, nf)

    if kernel == "paged_decode_quant":
        B, ctx = int(shape["b"]), int(shape["c"])
        if ctx % page_block:
            raise ValueError(f"ctx {ctx} not a multiple of page_block "
                             f"{page_block}")
        mb = ctx // page_block
        nb = 1 + B * mb                                # block 0 = null block

        def pq(q, kp, vp, ks, vs, tables, kv_len):
            # the models' int8 read: dequantize the pools, then the one
            # paged decode kernel
            return ops.paged_decode_attention(
                q, ref.dequantize_kv(kp, ks, q.dtype),
                ref.dequantize_kv(vp, vs, q.dtype), tables, kv_len, **bk)

        pq = jax.jit(pq)
        q = jnp.asarray(rng.normal(size=(B, Hq, 1, Dh)), jnp.float32)
        kp = jnp.asarray(rng.integers(-127, 128, size=(nb, Hkv, page_block, Dh)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, size=(nb, Hkv, page_block, Dh)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.005, 0.02,
                                     size=(nb, Hkv, page_block, 1)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02,
                                     size=(nb, Hkv, page_block, 1)), jnp.float32)
        tables = jnp.asarray(np.arange(1, 1 + B * mb).reshape(B, mb), jnp.int32)
        kv_len = jnp.full((B,), ctx, jnp.int32)
        t, nf = _time(pq, q, kp, vp, ks, vs, tables, kv_len, iters=iters)
        # attention matmuls + the per-element dequantize multiplies
        flops = 4.0 * B * Hq * ctx * Dh + 4.0 * B * Hkv * ctx * Dh
        byts = (1.0 * 2.0 * B * Hkv * ctx * Dh        # int8 K/V pool reads
                + isz * 2.0 * B * Hkv * ctx           # scale columns
                + isz * 2.0 * B * Hq * Dh             # q + out
                # the dequantized f32 copies of BOTH pools (write + re-read
                # by the kernel)
                + isz * 4.0 * B * Hkv * ctx * Dh)
        if materializes_scores:
            byts += isz * 3.0 * B * Hq * ctx
        return KernelSample("paged_decode_quant", flops, byts, float(ctx), t, nf)

    if kernel == "kv_migrate":
        # Device-side KV-block migration (serving.batching.migrate_kv_blocks):
        # gather `blocks` K/V blocks from a source paged pool, scatter them
        # into a destination pool. Pure data movement — flops 0, so the
        # sample constrains the calibration's effective memory bandwidth
        # (mem_eff), which migration_seconds reads through oracle.resolve.
        nblk = int(shape.get("blocks", shape.get("b", 8)))
        nb = 1 + nblk                                  # block 0 = null block
        sk = jnp.asarray(rng.normal(size=(nb, Hkv, page_block, Dh)), jnp.float32)
        sv = jnp.asarray(rng.normal(size=(nb, Hkv, page_block, Dh)), jnp.float32)
        dk = jnp.zeros_like(sk)
        dv = jnp.zeros_like(sv)
        ids = jnp.arange(1, 1 + nblk, dtype=jnp.int32)

        @jax.jit
        def mv(sk, sv, dk, dv, ids):
            return dk.at[ids].set(sk[ids]), dv.at[ids].set(sv[ids])

        t, nf = _time(mv, sk, sv, dk, dv, ids, iters=iters)
        # K+V payload, read once from the source pool + written once into
        # the destination pool
        byts = isz * 2.0 * 2.0 * nblk * Hkv * page_block * Dh
        return KernelSample("kv_migrate", 0.0, byts, 0.0, t, nf)

    if kernel == "ssm_scan":
        B, S = int(shape.get("b", 1)), int(shape["s"])
        H, P, N = heads, ssm_head_dim, state_dim
        chunk = int(p.get("chunk", 128))               # executed-FLOPs driver
        ss = jax.jit(functools.partial(ops.ssd_scan, chunk=chunk, **bk))
        x = jnp.asarray(rng.normal(size=(B, H, S, P)), jnp.float32)
        dt = jnp.asarray(rng.uniform(0.001, 0.2, size=(B, H, S)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 4.0, size=(H,)), jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
        t, nf = _time(ss, x, dt, A, Bm, Cm, iters=iters)
        # chunked dual form: CB^T + att@x per chunk, C@state + state update
        flops = 2.0 * B * H * S * (chunk * N + chunk * P + 2.0 * N * P)
        byts = isz * (2.0 * B * H * S * P + 2.0 * B * S * N + B * H * S)
        return KernelSample("ssm_scan", flops, byts, 0.0, t, nf)

    raise KeyError(f"unknown kernel {kernel!r}")


def kernel_phase_samples(*, prefill_lens: Sequence[int] = (128, 256, 512, 1024),
                         decode_ctxs: Sequence[int] = (128, 256, 512, 1024,
                                                       2048, 4096),
                         ssm_lens: Sequence[int] = (256, 512, 1024),
                         paged_ctxs: Sequence[int] = (),
                         migrate_blocks: Sequence[int] = (),
                         batch: int = 1, heads: int = 4, kv_heads: int = 2,
                         head_dim: int = 64, state_dim: int = 64,
                         ssm_head_dim: int = 64, iters: int = 5,
                         backend: Optional[str] = None,
                         seed: int = 0, tuned=None) -> List[KernelSample]:
    """Time the real kernels behind the serving stack and return samples the
    roofline calibration can fit (``fit_calibration``).

    Kernels go through ``kernels.ops`` backend dispatch: compiled Pallas on
    TPU, the structurally identical jnp path elsewhere — so the same command
    calibrates whichever hardware it runs on. FLOPs/bytes are the kernel's
    analytic work for the timed shape; ``ctx`` is the context length that
    drives ``SystemProfile.sat_ctx`` degradation (0 for the SSD scan, whose
    running state is constant-size).

    ``tuned`` (an ``autotune.AutotuneCache``) re-measures every cell with its
    autotuned parameters pinned explicitly — the re-measurement feed for the
    oracle-refresh parity gate. None keeps the dispatch defaults.
    """
    from repro.kernels import autotune as AT
    b = ops.resolve_backend(backend or "auto")

    def tuned_params(kernel: str, **dims) -> Optional[Dict[str, object]]:
        if tuned is None:
            return None
        return tuned.resolve(kernel, b, AT.shape_bucket(kernel, **dims))

    dims = dict(heads=heads, kv_heads=kv_heads, head_dim=head_dim,
                state_dim=state_dim, ssm_head_dim=ssm_head_dim)
    out: List[KernelSample] = []
    for S in prefill_lens:
        out.append(time_kernel("flash_attention", {"b": batch, "s": S},
                               params=tuned_params("flash_attention", s=S),
                               backend=backend, iters=iters, seed=seed, **dims))
    for ctx in decode_ctxs:
        out.append(time_kernel("decode_attention", {"b": batch, "c": ctx},
                               params=tuned_params("decode_attention",
                                                   b=batch, c=ctx),
                               backend=backend, iters=iters, seed=seed, **dims))
    for ctx in paged_ctxs:
        out.append(time_kernel("paged_decode_quant", {"b": batch, "c": ctx},
                               backend=backend, iters=iters, seed=seed, **dims))
    for nblk in migrate_blocks:
        out.append(time_kernel("kv_migrate", {"blocks": nblk},
                               backend=backend, iters=iters, seed=seed, **dims))
    for S in ssm_lens:
        out.append(time_kernel("ssm_scan", {"b": batch, "s": S},
                               params=tuned_params("ssm_scan", s=S),
                               backend=backend, iters=iters, seed=seed, **dims))
    return out


def serving_microbench() -> List:
    rows = []
    cfg = get_config("smollm-360m").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, max_len=128)
    batch = {"tokens": jnp.zeros((4, 32), jnp.int32)}
    logits, cache = eng.prefill(batch)
    rows.append(["prefill_b4_s32", _bench(lambda: eng.prefill(batch)[0])])
    tok = jnp.zeros((4, 1), jnp.int32)
    rows.append(["decode_b4", _bench(lambda: eng.decode(tok, cache)[0])])

    opt = OPT.AdamWConfig()
    step = jax.jit(make_train_step(cfg, opt))
    state = OPT.init_state(params)
    tb = next(D.uniform_stream(cfg, 4, 64, 1))
    rows.append(["train_step_b4_s64",
                 _bench(lambda: step(params, state, tb)[2]["loss"])])
    return rows
