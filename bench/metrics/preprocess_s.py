"""preprocess_s -- layer: planning and weight DP (core/batch.py,
core/estimator.py, core/weights.py); source: program_span (the
``preprocess`` stage); moves: setup_s.

Seconds of the ``preprocess`` stage during set-up: tree choice and the
weight DP of every candidate tree of the standing pairs."""


def read(ctx):
    s = ctx.setup_scrape["stage"].get("preprocess")
    return s[0] if s and s[1] > 0 else None
