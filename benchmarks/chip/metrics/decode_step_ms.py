"""Device time per execution of the jitted ``decode_step_paged``, from the
trace."""
import numpy as np


def read(run):
    d = run.trace.step_ns.get("decode_step_paged") if run.trace else None
    return float(np.mean(d)) * 1e-6 if d else None
