"""sampler_ns_per_sample -- layer: sampler and validator window program
(core/sampler.py); source: device_trace; moves: samples_per_s.

Device time of the innermost ops under the ``sample`` named scope in the
captured ``*window*`` executions (``bench/xplane.py``), in ns, over the
samples on the captured ``engine.dispatch`` annotations.  None where the
capture has no such scope or annotation."""
import os

from bench import xplane

PROFILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out", "profile")


def read(ctx):
    return xplane.ns_per_sample(ctx, PROFILE, ("sample",))
