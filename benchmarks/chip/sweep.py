"""The sweep that finds the highest rate a Poisson cell sustains.

    python benchmarks/chip/sweep.py --workload qwen2_5_3b.chat \\
        --rates 3,4,5,6 --seconds 20 --seed 5

Serves the cell's traffic at each rate in turn, on a fresh router over the
same weights, and prints one JSON line per rate: the end-to-end numbers,
the TTFT of the window's first and last thirds (a backlog that grows shows
as the last third waiting longer) and what was still queued at the close.
It only informs the fixed rate written into the cell's file
(``cells/<cell>.json``); the benchmark never searches for a rate.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from chipbench import e2e, harness, spec
    from chipbench.run import open_chips
    from chipbench.traffic import Traffic
    from chipbench.weights import make_params
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    sv = conf["serving"]
    open_chips(cell["chips"])
    params = make_params(conf, args.seed, sv["dtype"])
    for rate in (float(r) for r in args.rates.split(",")):
        router = harness.build_router(conf, params)
        harness.warm_up(router, conf)
        traffic = Traffic(mix, rate_per_s=rate, max_len=sv["max_len"],
                          vocab=conf["vocab_size"], seed=args.seed)
        _, served = harness.serve_window(router, traffic, conf, args.seconds)
        s = e2e.summary(served.records, args.seconds, served.end_s)
        thirds = {}
        for k, lo in (("first", 0.0), ("last", 2 / 3)):
            w = [(r.token_s[0] if r.token_s else served.end_s) - r.due_s
                 for r in served.records if r.in_window
                 and lo * args.seconds <= r.due_s < (lo + 1 / 3) * args.seconds]
            thirds[f"ttft_p50_ms_{k}_third"] = 1e3 * float(np.median(w)) if w else None
        queued = sum(len(cb.queue) for cb in router.batchers.values())
        offered = sum(r.n for r in served.records if r.in_window) / args.seconds
        print(json.dumps(dict(s, rate_per_s=rate, offered_tok_s=offered,
                              queued_at_close=queued,
                              end_of_observation_s=served.end_s, **thirds)),
              flush=True)
        del router, served
        gc.collect()
        jax.block_until_ready(params)
    return 0


if __name__ == "__main__":
    sys.exit(main())
