"""One run of one cell: set-up, the window, the readings and the check."""
from __future__ import annotations

import gc
import os
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from chipbench import check, e2e, harness, spec
from chipbench import trace as T
from chipbench.peaks import Peaks
from chipbench.traffic import Traffic
from chipbench.weights import make_params

PROFILE_S = 4.0          # a traced run profiles the window's last seconds


@dataclass
class RunView:
    """What a per-layer metric's reader may read."""
    conf: dict
    served: harness.Served
    trace: Optional[T.TraceSummary]
    peaks: Optional[Peaks]
    memory_peak_bytes: Optional[int]
    profile_from_s: float    # host-clock metrics use what came before it


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def open_chips(chips: int):
    """The TPU devices, after refusing anything else: another platform,
    fewer chips than the cell asks for, a kernel dispatch that is not the
    compiled Pallas one. Turns the compile cache on."""
    from repro.kernels import ops
    from repro.launch.envcfg import use_compile_cache
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if os.environ.get("REPRO_KERNEL_BACKEND") or ops.resolve_backend() != "pallas":
        raise SystemExit(f"kernel dispatch resolves to "
                         f"{ops.resolve_backend()!r}, not 'pallas'")
    use_compile_cache()
    return devs


def _memory_peak(dev) -> Optional[int]:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_cell(cell: dict, conf: dict, mix: dict, *, rate_per_s: Optional[float],
             bench: dict, seed: int, seconds: float, trace: bool, t_start: float,
             peaks: Optional[Peaks], controls=()) -> dict:
    """Serve the cell for ``seconds`` and check what came out. Returns the
    result object that the command prints last, or with ``controls`` (the
    lower precisions to read beside the program) the comparison's
    readings."""
    dev = jax.devices()[0]
    sv = conf["serving"]
    params = make_params(conf, seed, sv["dtype"])
    jax.block_until_ready(params)
    log("params_made_s", time.perf_counter() - t_start)
    router = harness.build_router(conf, params)
    harness.warm_up(router, conf)
    log("warmed_up_s", time.perf_counter() - t_start)
    traffic = Traffic(mix, rate_per_s=rate_per_s, max_len=sv["max_len"],
                      vocab=conf["vocab_size"], seed=seed)
    profile_s = min(PROFILE_S, seconds / 2)
    tracer = harness.Tracer(seconds, profile_s) if trace else None
    counter = harness.CompileCounter()
    counter.on = True
    t0, served = harness.serve_window(router, traffic, conf, seconds,
                                      tracer=tracer)
    counter.on = False
    setup_s = t0 - t_start
    mem_peak = _memory_peak(dev)

    summary = e2e.summary(served.records, seconds, served.end_s)
    log("setup_s", setup_s)
    log("compiles_in_window", len(counter.events), counter.events[:5])
    log("ticks", served.ticks, "end_of_observation_s", served.end_s)
    late = np.asarray(served.late_s or [0.0])
    log("generator_late_ms p50 p95 max", 1e3 * np.median(late),
        1e3 * np.percentile(late, 95), 1e3 * late.max())
    for k, v in summary.items():
        log(k, v)
    split = {}
    for r in e2e.window_records(served.records):
        split[r.pool] = split.get(r.pool, 0) + 1
    log("routing_split", split)
    for name, cb in router.batchers.items():
        log("pool_stats", name, cb.stats())
    log("memory_peak_bytes", mem_peak)

    summ = None
    if tracer is not None:
        summ = T.reduce(tracer.events())
        shutil.rmtree(tracer.logdir, ignore_errors=True)
    del router                       # free the pools before the reference
    gc.collect()

    sample = check.pick_sample(served.records, seed)
    log("check_sample", [(r.index, r.pool, r.m, r.n) for r in sample])
    log("check_coverage", check.coverage(sample, conf))
    t = time.perf_counter()
    result = check.compare(params, conf, sample, controls=controls)
    log("check_s", time.perf_counter() - t)
    log("per_request_gap_sd", result["per_request_gap_sd"])
    if controls:
        return dict(result, seed=seed, summary=summary, setup_s=setup_s)
    ok, numbers = check.verdict(result, conf)

    view = RunView(conf, served, summ, peaks, mem_peak,
                   profile_from_s=seconds - profile_s if trace else seconds)
    metrics = {}
    if trace:
        for m in spec.metrics_of(bench, cell["name"], "per_layer"):
            value = spec.load_reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(summary, setup_s=setup_s)
        for m in spec.metrics_of(bench, cell["name"], "end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    out = {"correct": ok, "attempted": summary["requests_in_window"],
           "failed": summary["failed"], "metrics": metrics, "device": device}
    if summ is not None:
        device.update(busy_s=summ.busy_s, window_s=summ.window_s)
        out["breakdown"] = summ.breakdown()
    out["check"] = numbers
    for name, num in numbers.items():
        log(f"check {name} {num['value']} limit {num['limit']}")
    return out
