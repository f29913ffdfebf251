"""95th percentile of due -> the tick after which the request held a lane
(``PagedContinuousBatcher._admit``), over the window's requests due before
the profile started. One never admitted counts its wait until the end."""
from chipbench.e2e import p95


def read(run):
    w = [(r.admit_s if r.admit_s is not None else run.served.end_s) - r.due_s
         for r in run.served.records
         if r.in_window and r.due_s < run.profile_from_s]
    return 1e3 * p95(w) if w else None
