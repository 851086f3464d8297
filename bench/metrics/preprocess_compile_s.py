"""preprocess_compile_s -- layer: planning and weight DP (core/batch.py,
core/weights.py); source: program_span (the ``compile.preprocess``
stage); moves: setup_s.

Seconds the server spent tracing, lowering and compiling (or loading
from the compile cache) inside the ``preprocess`` stage during set-up:
the candidate trees' weight-DP programs.  The union over threads on the
wall clock (``repro/obs/compiles.py``), so it is part of
``preprocess_s``.  None where the server reports no such stage."""


def read(ctx):
    s = ctx.setup_scrape["stage"].get("compile.preprocess")
    return s[0] if s and s[1] > 0 else None
