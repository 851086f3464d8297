"""window_compile_s -- layer: sampler and validator window program
(core/engine.py, core/sampler.py, core/validate.py); source:
program_span (the ``compile.device`` and ``compile.dispatch`` stages);
moves: setup_s.

Seconds the server spent tracing, lowering and compiling (or loading
from the compile cache) the window programs during set-up: compiles
inside the engine's ``dispatch`` and ``device`` stages.  None where the
server reports neither stage."""


def read(ctx):
    got = [ctx.setup_scrape["stage"].get(f"compile.{s}")
           for s in ("device", "dispatch")]
    got = [s for s in got if s and s[1] > 0]
    return sum(s[0] for s in got) if got else None
