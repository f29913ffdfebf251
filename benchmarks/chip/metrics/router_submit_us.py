"""Mean host time of ``FleetRouter.submit`` (routing, pricing and queueing
one request), on the host clock around each call, for the requests due
before the profile started."""
import numpy as np


def read(run):
    d = [dt for due_s, dt in run.served.submit_s if due_s < run.profile_from_s]
    return float(np.mean(d)) * 1e6 if d else None
