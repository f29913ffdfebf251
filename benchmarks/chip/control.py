"""Readings that set the limit of the check: the program's widest served-token
gap, and the gap of the control (the reference computed in a lower
precision), on many seeds in one process.

    python benchmarks/chip/control.py --workload qwen2_5_3b.chat \\
        --seeds 11,12,13 --seconds 20 --controls int8,fp8

Each seed is a whole run at the cell's own load and sizes (set-up, window,
comparison), with the control computed at each position of the same
prompts and served tokens. Prints one JSON line per seed. The benchmark's
own runs never compute the control.
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


def main(argv=None) -> int:
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)

    from chipbench import spec
    from chipbench.run import open_chips, run_cell
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    open_chips(cell["chips"])
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell(cell, conf, mix, rate_per_s=spec.cell_rate(cell),
                       bench=bench, seed=seed,
                       seconds=args.seconds, trace=False, t_start=t_start,
                       peaks=None, controls=tuple(args.controls.split(",")))
        print(json.dumps(out), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
