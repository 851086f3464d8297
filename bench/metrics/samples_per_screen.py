"""samples_per_screen -- layer: adaptive budget (api/session.py growth
rounds and batch-means RSE); source: program_counter (the served ``k`` of
each reply); moves: samples_per_s.screen.

Mean samples the server drew per answer of the window: the budget its
stopping rule reached for the stated error.  None where no request was
answered."""


def read(ctx):
    ks = [rec.reply["k"] for rec in ctx.window.records if rec.ok]
    return sum(ks) / len(ks) if ks else None
