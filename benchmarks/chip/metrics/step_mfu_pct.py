"""Model FLOPs of the paged prefill and decode executions in the traced
window over their device time at the bf16 peak. FLOPs per execution are the
mean over the calls the harness issued while profiling (live lanes and
valid chunk tokens only, ``chipbench.flops``)."""
import numpy as np

from chipbench import flops


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    per_call = {"decode_step_paged": [], "prefill_paged_chunk": []}
    for kind, arg in run.served.traced_calls:
        if kind == "decode":
            per_call["decode_step_paged"].append(flops.decode_flops(run.conf, arg))
        else:
            per_call["prefill_paged_chunk"].append(
                flops.prefill_flops(run.conf, *arg))
    work = time_ns = 0.0
    for module, f in per_call.items():
        d = run.trace.step_ns.get(module) or []
        if f and d:
            work += float(np.mean(f)) * len(d)
            time_ns += float(np.sum(d))
    if time_ns == 0:
        return None
    return 100.0 * work / (time_ns * 1e-9 * run.peaks.bf16_flop_per_s)
