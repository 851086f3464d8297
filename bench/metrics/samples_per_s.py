"""samples_per_s (host clock): samples of every answered fixed-budget
request of the window over ``--seconds``; a request straddling an edge
of the window counts by the share of its send-to-answer time inside."""
from bench.traffic import in_window_share


def read(ctx):
    w = ctx.window
    done = sum(rec.reply["k"] * in_window_share(rec, w.t0, w.t1)
               for rec in w.records if rec.ok)
    return done / ctx.seconds
