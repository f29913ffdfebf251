"""95th percentile of due -> first token over the window's requests due
before the profile started: the cell's time to first token, which runs of
one cell spread too widely to bound end to end. One that never got its
first token counts its wait until the end of observation."""
from chipbench.e2e import p95


def read(run):
    w = [(r.token_s[0] if r.token_s else run.served.end_s) - r.due_s
         for r in run.served.records
         if r.in_window and r.due_s < run.profile_from_s]
    return 1e3 * p95(w) if w else None
