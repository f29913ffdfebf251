"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python benchmarks/chip/bench.py --workload qwen2_5_3b.chat --seed 7 \\
        --seconds 40 --trace 0

Builds the served path from the cell's configuration file over weights made
from the seed, warms up the shapes the cell's traffic uses, offers the
traffic open loop for ``--seconds``, then checks what the window served
against the plain float32 reference. ``--trace 1`` also profiles the
window's last seconds and reports the per-layer metrics instead of the
end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``check``: each number compared with its limit).
Everything else goes to standard error. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
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
    # the compile cache lives in the checkout, at a path that never moves
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import spec
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    conf = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(cell["traffic"])

    from chipbench.peaks import peaks_for
    from chipbench.run import open_chips, run_cell
    devs = open_chips(cell["chips"])
    out = run_cell(cell, conf, mix, rate_per_s=spec.cell_rate(cell),
                   bench=bench, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   t_start=T_START, peaks=peaks_for(devs[0].device_kind))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
