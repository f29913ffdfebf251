"""Share of its roofline that the Pallas paged decode attention kernel
reaches: the larger of FLOPs over peak and bytes over HBM bandwidth, for
the live contexts of the decode calls issued while profiling
(``chipbench.flops.paged_attn_cost``, one call per layer), over the
kernel's mean device time in the trace."""
import numpy as np

from chipbench import flops


def read(run):
    if run.trace is None or run.peaks is None or not run.trace.kernel_ns:
        return None
    bound_s = [max(f / run.peaks.bf16_flop_per_s, b / run.peaks.hbm_bytes_per_s)
               for f, b in (flops.paged_attn_cost(run.conf, arg)
                            for kind, arg in run.served.traced_calls
                            if kind == "decode")]
    if not bound_s:
        return None
    return 100.0 * float(np.mean(bound_s)) / (
        float(np.mean(run.trace.kernel_ns)) * 1e-9)
