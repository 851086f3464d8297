"""queue_wait_ms -- layer: gateway intake and scheduler (gateway/serve.py
dispatcher, api/session.py submit to first drain); source: program_span
(the ``queue_wait`` stage); moves: samples_per_s.screen.

Mean ``queue_wait`` seconds per answered request of the window, in ms:
the growth of the stage's sum between the scrapes before and after the
window over the requests answered.  A request of one client waits there
while another client's drain runs its growth rounds.  None where the
server reports no such stage or no request was answered."""


def read(ctx):
    if not ctx.window_scrapes:
        return None
    m0, m1 = (s["stage"].get("queue_wait") for s in ctx.window_scrapes)
    answered = sum(1 for rec in ctx.window.records if rec.reply)
    if not m1 or not answered:
        return None
    return 1e3 * (m1[0] - (m0[0] if m0 else 0.0)) / answered
