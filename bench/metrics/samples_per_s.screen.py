"""samples_per_s.screen (host clock): samples of every answered request
of the window over the time from the window's start to its last answer.
The clients send for ``--seconds`` and every request they sent is
awaited, so this is all of the window's work over all the time it took;
two closed-loop clients keep the device busy throughout, so it is the
rate of completed work.  Not ``samples_per_s``'s pro rata share of each
send-to-answer time: with two clients that time holds the wait behind
the other client's drain, and answers of up to 2^20 samples straddle
the close."""


def read(ctx):
    w = ctx.window
    done = [rec for rec in w.records if rec.ok]
    if not done:
        return None
    end = max(rec.answered for rec in done)
    return sum(rec.reply["k"] for rec in done) / (end - w.t0)
