"""Device time per execution of the jitted ``prefill_paged_chunk``, from the
trace."""
import numpy as np


def read(run):
    d = run.trace.step_ns.get("prefill_paged_chunk") if run.trace else None
    return float(np.mean(d)) * 1e-6 if d else None
