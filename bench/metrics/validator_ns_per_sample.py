"""validator_ns_per_sample -- layer: sampler and validator window program
(core/validate.py, the cohort count in core/sampler.py and the window's
accumulation in core/engine.py); source: device_trace; moves:
samples_per_s.

Device time of the innermost ops under the ``validate`` and ``score``
named scopes in the captured ``*window*`` executions
(``bench/xplane.py``), in ns, over the samples on the captured
``engine.dispatch`` annotations.  None where the capture has no such
scope or annotation."""
import os

from bench import xplane

PROFILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out", "profile")


def read(ctx):
    return xplane.ns_per_sample(ctx, PROFILE, ("validate", "score"))
