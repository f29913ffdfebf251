"""Autotune the serving kernels and regenerate the pricing oracle from the
tuned timings.

Full mode runs the grid search (``repro.kernels.autotune``) over the tracked
kernel/shape cells on the benchmark backend ("ref" — the jnp execution path
this container actually serves with; on a TPU host the same command tunes the
compiled Pallas kernels), then:

  * persists the winners to ``experiments/autotune/<profile>__<backend>.json``
    (env-fingerprinted; stale-env caches refuse to load),
  * rebuilds the pricing grid via ``TableOracle.from_autotune`` and checks the
    refreshed grid prices RE-MEASURED tuned kernels within the measured
    calibration tolerance (the measure -> fit -> route loop, closed),
  * records per-cell tuned-vs-default times in ``BENCH_kernels.json`` at the
    repo root, gated at a >= 1.15x geometric-mean speedup.

``--smoke`` is the CI gate: a tiny grid in a temp dir must round-trip the
cache schema (including the stale-env refusal), satisfy per-cell
no-regression (winner never slower than the default on the measured grid),
refresh the oracle within tolerance, and find a well-formed committed
``BENCH_kernels.json`` whose recorded geomean clears the bar.

Run: PYTHONPATH=src python benchmarks/autotune_sweep.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import tempfile
from typing import Dict, List, Optional, Sequence

import numpy as np

try:
    from benchmarks.calibrate import HOST_CPU, MEASURED_REL_RMSE_BOUND
    from benchmarks.microbench import time_kernel
except ImportError:                      # standalone: benchmarks/ on sys.path
    from calibrate import HOST_CPU, MEASURED_REL_RMSE_BOUND
    from microbench import time_kernel
from repro.configs import get_config
from repro.core.pricing import KernelSample, TableOracle, _predict, _rel_rmse
from repro.kernels import autotune as AT
from repro.launch import envcfg

BENCH_PATH = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_kernels.json")

GEOMEAN_SPEEDUP_GATE = 1.15
BENCH_BACKEND = "ref"
BENCH_MODEL = "qwen2.5-3b"

# The tracked configuration: the shape buckets the reduced serving stack
# actually hits, one cell per (kernel, bucket). decode_attention is absent
# on the ref backend (its only tunable, the split-KV tile, is a Pallas
# grid parameter) — the tuner skips kernels with empty candidate spaces.
TRACKED_SHAPES: Dict[str, Sequence[Dict[str, int]]] = {
    "flash_attention": ({"s": 1024}, {"s": 2048}),
    "ssm_scan": ({"s": 512}, {"s": 1024}),
}

SMOKE_SHAPES: Dict[str, Sequence[Dict[str, int]]] = {
    "flash_attention": ({"s": 128},),
    "ssm_scan": ({"s": 128},),
}

REQUIRED_KEYS = ("config", "env_digest", "cells", "geomean_speedup",
                 "oracle_refresh")
CELL_KEYS = ("kernel", "bucket", "params", "default_params", "t_default_s",
             "t_tuned_s", "speedup")


def _remeasure(cache: AT.AutotuneCache, *, iters: int,
               seed: int) -> List[KernelSample]:
    """Time every cache entry again with its WINNING params pinned — the
    independent measurement the refreshed oracle is gated against."""
    out = []
    for e in sorted(cache.entries.values(), key=lambda e: e.key()):
        out.append(time_kernel(e.kernel, e.shape, params=e.params,
                               backend=cache.backend, iters=iters,
                               seed=seed + 1))   # fresh data, same shapes
    return out


def _oracle_refresh(cache: AT.AutotuneCache, *, iters: int,
                    seed: int) -> Dict:
    """Rebuild the pricing grid from tuned timings and bound its error
    against re-measured tuned kernels."""
    cfg = get_config(BENCH_MODEL)
    oracle = TableOracle.from_autotune(cfg, HOST_CPU, cache)
    cal = oracle.calibration
    remeasured = _remeasure(cache, iters=iters, seed=seed)
    pred = _predict(remeasured, HOST_CPU, cal.compute_eff, cal.mem_eff,
                    cal.sat_ctx, cal.overhead_s)
    t = np.array([s.t_s for s in remeasured])
    remeasured_rmse = _rel_rmse(pred, t)
    return {
        "fit_rel_rmse": cal.fit_rel_rmse,
        "remeasured_rel_rmse": remeasured_rmse,
        "bound": MEASURED_REL_RMSE_BOUND,
        "compute_eff": cal.compute_eff,
        "mem_eff": cal.mem_eff,
        "sat_ctx": cal.sat_ctx,
        "overhead_s": cal.overhead_s,
        "n_samples": cal.n_samples,
    }


def _cells(cache: AT.AutotuneCache) -> List[Dict]:
    rows = []
    for e in sorted(cache.entries.values(), key=lambda e: e.key()):
        rows.append({
            "kernel": e.kernel, "bucket": e.bucket, "shape": e.shape,
            "params": e.params,
            "default_params": AT.default_params(e.kernel, e.backend),
            "t_default_s": e.t_default_s, "t_tuned_s": e.t_s,
            "noise_frac": round(e.noise_frac, 4),
            "speedup": round(e.speedup, 3),
        })
    return rows


def bench(*, iters: int = 7, seed: int = 0,
          out_dir: Optional[str] = None) -> Dict:
    """Tune the tracked cells, refresh the oracle, write both artifacts."""
    cache_dir = out_dir if out_dir is not None else AT.CACHE_DIR
    print(f"autotuning {sum(len(v) for v in TRACKED_SHAPES.values())} cells "
          f"on backend {BENCH_BACKEND!r} (iters={iters}) ...", flush=True)
    cache = AT.autotune(TRACKED_SHAPES, profile=HOST_CPU.name,
                        backend=BENCH_BACKEND, iters=iters, seed=seed,
                        verbose=True)
    cpath = cache.dump(AT.cache_path(HOST_CPU.name, BENCH_BACKEND, cache_dir))
    print(f"cache -> {os.path.relpath(cpath)}")

    refresh = _oracle_refresh(cache, iters=iters, seed=seed)
    geo = cache.geomean_speedup()
    out = {
        "config": {
            "model": BENCH_MODEL, "profile": HOST_CPU.name,
            "backend": BENCH_BACKEND, "seed": seed, "iters": iters,
            "shapes": {k: list(v) for k, v in TRACKED_SHAPES.items()},
            "gate_geomean": GEOMEAN_SPEEDUP_GATE,
        },
        "env_digest": envcfg.fingerprint_digest(cache.env),
        "cells": _cells(cache),
        "geomean_speedup": round(geo, 3),
        "oracle_refresh": refresh,
    }
    bench_path = os.path.join(out_dir, "BENCH_kernels.json") \
        if out_dir is not None else BENCH_PATH
    with open(bench_path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    for c in out["cells"]:
        print(f"  {c['kernel']}/{c['bucket']}: {c['params']} "
              f"{c['t_default_s'] * 1e3:.2f} -> {c['t_tuned_s'] * 1e3:.2f} ms "
              f"({c['speedup']}x)")
    print(f"geomean speedup {geo:.3f}x (gate {GEOMEAN_SPEEDUP_GATE}x); "
          f"oracle refresh rel-RMSE fit={refresh['fit_rel_rmse']:.3f} "
          f"remeasured={refresh['remeasured_rel_rmse']:.3f} "
          f"(bound {MEASURED_REL_RMSE_BOUND}) -> "
          f"{os.path.relpath(bench_path)}")
    assert geo >= GEOMEAN_SPEEDUP_GATE, (
        f"tuned geomean speedup {geo:.3f}x below the "
        f"{GEOMEAN_SPEEDUP_GATE}x gate")
    assert refresh["remeasured_rel_rmse"] < MEASURED_REL_RMSE_BOUND, (
        f"tuned-grid pricing off by {refresh['remeasured_rel_rmse']:.3f} "
        f"rel-RMSE vs re-measured tuned kernels "
        f"(bound {MEASURED_REL_RMSE_BOUND})")
    return out


def smoke() -> None:
    """CI gate: schema round-trip + stale-env refusal + no-regression +
    oracle-refresh parity on a tiny grid, plus the committed artifact."""
    with tempfile.TemporaryDirectory() as tmp:
        cache = AT.autotune(SMOKE_SHAPES, profile=HOST_CPU.name,
                            backend=BENCH_BACKEND, iters=2, seed=0)
        n_cells = sum(len(v) for v in SMOKE_SHAPES.values())
        assert len(cache.entries) == n_cells, (len(cache.entries), n_cells)

        # schema round-trip: dump -> load -> identical resolution
        path = cache.dump(AT.cache_path(HOST_CPU.name, BENCH_BACKEND, tmp))
        loaded = AT.AutotuneCache.load(path)
        for e in cache.entries.values():
            assert loaded.resolve(e.kernel, e.backend, e.bucket) == e.params
        assert loaded.to_json() == cache.to_json()

        # stale-env refusal: perturb the fingerprint, reload must raise
        with open(path) as f:
            data = json.load(f)
        data["env"]["jax"] = "0.0.0-stale"
        data["env_digest"] = envcfg.fingerprint_digest(data["env"])
        stale_path = os.path.join(tmp, "stale.json")
        with open(stale_path, "w") as f:
            json.dump(data, f)
        try:
            AT.AutotuneCache.load(stale_path)
        except AT.StaleCacheError:
            pass
        else:
            raise AssertionError("stale-env cache loaded without error")
        AT.AutotuneCache.load(stale_path, require_env=False)  # escape hatch

        # no-regression: the default is in every candidate grid, so the
        # winner can never be slower than it on the measured grid
        for e in cache.entries.values():
            assert e.t_s <= e.t_default_s, (
                f"{e.key()}: tuned {e.t_s} > default {e.t_default_s}")

        # oracle-refresh parity on the tiny grid
        refresh = _oracle_refresh(cache, iters=2, seed=0)
        assert refresh["remeasured_rel_rmse"] < MEASURED_REL_RMSE_BOUND, (
            f"smoke oracle refresh rel-RMSE "
            f"{refresh['remeasured_rel_rmse']:.3f} >= "
            f"{MEASURED_REL_RMSE_BOUND}")

    # the committed tracked artifact must exist, be well-formed, and clear
    # the recorded gate (the full sweep is too slow for CI)
    assert os.path.exists(BENCH_PATH), (
        "BENCH_kernels.json missing: run benchmarks/autotune_sweep.py "
        "(full mode)")
    with open(BENCH_PATH) as f:
        rec = json.load(f)
    for k in REQUIRED_KEYS:
        assert k in rec, f"BENCH_kernels.json missing key {k!r}"
    assert rec["cells"], "BENCH_kernels.json has no cells"
    for c in rec["cells"]:
        for k in CELL_KEYS:
            assert k in c, f"BENCH_kernels.json cell missing {k!r}"
        assert c["t_tuned_s"] <= c["t_default_s"] * 1.0001, (
            f"recorded cell {c['kernel']}/{c['bucket']} regressed")
    geo = math.exp(sum(math.log(c["speedup"]) for c in rec["cells"])
                   / len(rec["cells"]))
    assert abs(geo - rec["geomean_speedup"]) < 0.01, (
        "recorded geomean inconsistent with its cells")
    assert rec["geomean_speedup"] >= GEOMEAN_SPEEDUP_GATE, (
        f"recorded geomean {rec['geomean_speedup']}x below "
        f"{GEOMEAN_SPEEDUP_GATE}x")
    assert rec["oracle_refresh"]["remeasured_rel_rmse"] < \
        MEASURED_REL_RMSE_BOUND
    print(f"autotune smoke OK: {len(rec['cells'])} tracked cells, recorded "
          f"geomean {rec['geomean_speedup']}x, oracle refresh rel-RMSE "
          f"{rec['oracle_refresh']['remeasured_rel_rmse']:.3f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iters", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-dir", default=None,
                    help="redirect both artifacts (default: tracked paths)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: tiny grid in a temp dir + committed "
                         "artifact schema")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    bench(iters=args.iters, seed=args.seed, out_dir=args.out_dir)


if __name__ == "__main__":
    main()
