"""Serving entry point: hybrid-fleet router + real JAX engines.

``python -m repro.launch.serve --arch smollm-360m --requests 50 [--full-config]``

Routes an Alpaca-like request stream across an (efficiency, performance) pool
pair with the paper's scheduler, executes every request on the JAX engine
(through paged continuous batchers for the families the paged cache
supports), and prints the fleet energy/runtime report.
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.scheduler import kv_blocks_needed
from repro.core.systems import paper_fleet, tpu_fleet
from repro.core.workload import sample_workload
from repro.launch.envcfg import use_compile_cache
from repro.models import model as M
from repro.serving.engine import InferenceEngine
from repro.serving.router import FleetRouter

BLOCK_SIZE = 16                 # tokens per KV block of the paged pools


def build_router(arch: str, *, full_config: bool = False,
                 policy: str = "threshold", fleet: str = "tpu", t_in: int = 32,
                 lam: float = 1.0, seed: int = 0, max_len: int = 512,
                 lanes: int = 4) -> FleetRouter:
    """Build the served path for ``arch``: one ``InferenceEngine`` behind a
    ``FleetRouter`` over an (efficiency, performance) pool pair.

    Without ``full_config`` the model is the toy ``reduced()`` config in
    float32. With it, the published widths in bf16 (params and KV cache).
    Params come from a jitted ``init_params``, so the float32 normals behind
    each weight never exist at full size next to the bf16 result.

    Families with a paged cache get a ``PagedContinuousBatcher`` per pool,
    with enough blocks that every lane can hold ``max_len`` tokens at once;
    the others run each request through ``engine.generate``.
    """
    cfg = get_config(arch)
    if not full_config:
        cfg = cfg.reduced()
    dtype = jnp.bfloat16 if full_config else jnp.float32
    init = jax.jit(functools.partial(M.init_params, cfg, dtype=dtype))
    engine = InferenceEngine(cfg, init(jax.random.PRNGKey(seed)),
                             max_len=max_len, dtype=dtype)
    eff, perf = tpu_fleet() if fleet == "tpu" else paper_fleet()
    router = FleetRouter(cfg, {eff.name: eff, perf.name: perf},
                         {eff.name: engine, perf.name: engine},
                         policy=policy, t_in=t_in, lam=lam,
                         counts={eff.name: 4, perf.name: 1})
    if cfg.family in M.PAGED_FAMILIES:
        router.attach_batchers(
            lanes, paged=True, block_size=BLOCK_SIZE,
            num_blocks=lanes * kv_blocks_needed(max_len, BLOCK_SIZE) + 1)
    return router


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--full-config", action="store_true",
                    help="serve the published widths in bf16 (default: the "
                         "reduced config in float32)")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--policy", default="threshold",
                    choices=("threshold", "cost_optimal", "capacity_aware"))
    ap.add_argument("--t-in", type=int, default=32)
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--fleet", default="tpu", choices=("tpu", "paper"))
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    router = build_router(args.arch, full_config=args.full_config,
                          policy=args.policy, fleet=args.fleet, t_in=args.t_in,
                          lam=args.lam, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    routed = []
    for q in sample_workload(args.requests, seed=args.seed):
        m, n = min(q.m, 400), min(args.max_new_tokens, q.n)
        prompt = rng.integers(0, router.cfg.vocab_size, size=m)
        routed.append((m, n, router.submit(prompt, n)))
    router.drain()
    for m, n, res in routed:
        out = (res.output if res.request is None
               else np.asarray(res.request.out_tokens))
        print(f"req{res.rid:4d} m={m:5d} n={n:4d} "
              f"-> {res.pool:16s} E={res.energy_j:8.2f}J R={res.runtime_s:6.3f}s "
              f"tokens={out[:8]}")
    print("\nfleet report:")
    for pool, st in router.fleet_report().items():
        print(f"  {pool:16s} queries={st['queries']:4d} "
              f"energy={st['energy_j']:10.1f}J runtime={st['runtime_s']:8.2f}s")


if __name__ == "__main__":
    main()
