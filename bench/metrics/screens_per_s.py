"""screens_per_s -- layer: adaptive budget (api/session.py growth rounds
and batch-means RSE); source: host_clock; moves: samples_per_s.screen.

Answers of the window that meet their stated error (``rse`` reported and
at most the request's ``target_rse``) over ``--seconds``; a request
straddling an edge of the window counts by the share of its
send-to-answer time inside.  Under a closed loop it is the clients over
the mean time to an answer at the requested error.  Per-layer, not
bounded: the budget an answer takes swings with its two-window batch
means (PERF.md section 2)."""
from bench.traffic import in_window_share


def read(ctx):
    w = ctx.window
    return sum(in_window_share(rec, w.t0, w.t1)
               for rec in w.records if rec.met) / ctx.seconds
