"""One traced run of a cell that also reads the program's own spans.

    python benchmarks/chip/idle_split.py --workload qwen2_5_3b.chat \\
        --seed 7 --seconds 50 --save spans.json.gz

Runs the cell as ``bench.py --trace 1`` does, and from the same profile
reads the spans the serving path writes (``chipbench.spans``): the device's
idle time split into dispatch, host sync, tick bookkeeping and the rest
outside the ticks, and the live lanes of each decode call. ``bench.py``
reads the harness's spans only, so its result line holds none of these.

Prints, last, ``bench.py``'s result line with ``program_spans`` added
(``SpanSummary.readings``): ``idle_dispatch_pct``, ``idle_sync_pct``,
``idle_bookkeeping_pct`` and ``idle_outside_pct``, which sum to
``device_idle_pct``, and ``decode_batch_mean``. ``--save`` writes
``SAVE_MS`` of the profile (the harness's spans that lie in it, the
program's spans and the device events that overlap them) with
``chipbench.trace.save_events``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SAVE_MS = 300.0     # of the profile that ``--save`` keeps, from mid-window


def main(argv=None) -> int:
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)

    from chipbench import harness, spec
    from chipbench import spans as S
    from chipbench import trace as T
    from chipbench.peaks import peaks_for
    from chipbench.run import log, open_chips, run_cell

    kept = {}
    read_harness_events = harness.Tracer.events

    def events(tracer):
        """The harness's events, with the program's spans kept aside before
        ``run_cell`` deletes the profile."""
        kept["spans"] = S.read_spans(T.find_xplane(tracer.logdir))
        kept["events"] = read_harness_events(tracer)
        return kept["events"]

    harness.Tracer.events = events
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    devs = open_chips(cell["chips"])
    out = run_cell(cell, conf, mix, rate_per_s=spec.cell_rate(cell),
                   bench=bench, seed=args.seed, seconds=args.seconds,
                   trace=True, t_start=T_START,
                   peaks=peaks_for(devs[0].device_kind))

    summ = S.summarize(kept["events"], kept["spans"])
    found = summ.readings() if summ is not None else {}
    if summ is not None:
        log("idle_split_pct dispatch sync bookkeeping outside",
            *(summ.idle_pct(p) for p in S.PARTS), "device_idle_pct",
            out["metrics"].get("device_idle_pct", {}).get("value"))
        log("program_spans", len(kept["spans"]),
            "decode_calls", len(summ.decode_lanes))
    if args.save and summ is not None:
        ticks = sorted(e.start_ns for e in kept["events"]
                       if e.name.startswith("tick."))
        rec = S.excerpt(kept["events"] + kept["spans"],
                        ticks[len(ticks) // 2], SAVE_MS)
        Path(args.save).parent.mkdir(parents=True, exist_ok=True)
        T.save_events(rec, args.save)
        log("saved_events", len(rec), args.save)
    print(json.dumps(dict(out, program_spans=found)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
