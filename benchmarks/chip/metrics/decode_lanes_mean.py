"""Mean live lanes per batched decode call: lanes whose ``out_tokens`` grew
by a decode token in a tick, over the ticks before the profile started."""
import numpy as np


def read(run):
    n = [k for t_s, k in run.served.decode_calls if t_s < run.profile_from_s]
    return float(np.mean(n)) if n else None
