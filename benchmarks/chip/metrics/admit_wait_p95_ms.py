"""95th percentile of submit -> lane, from the stamps the batcher writes on
each request (``Request.queued_s`` in ``submit``, ``admitted_s`` when
``_admit`` gives it a lane; host clock), over the window's requests due
before the profile started. One never admitted counts its wait until the
end of observation. Reads nothing where the program writes no stamps."""
from chipbench.e2e import p95


def read(run):
    recs = [r for r in run.served.records
            if r.in_window and r.due_s < run.profile_from_s]
    if not recs or getattr(recs[0].req, "queued_s", None) is None:
        return None
    w = [r.req.admitted_s - r.req.queued_s if r.req.admitted_s is not None
         else run.served.end_s - r.submit_s for r in recs]
    return 1e3 * p95(w)
