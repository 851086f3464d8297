"""validator_ns_per_sample.screen -- layer: sampler and validator window
program; source: device_trace; moves: samples_per_s.screen.

The reading of ``validator_ns_per_sample`` (device ns of its scopes over the
samples on the captured ``engine.dispatch`` annotations, every stream
of a fused window counted), on the screen cells, where it moves
``samples_per_s.screen``."""
import os

from bench import xplane

PROFILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out", "profile")


def read(ctx):
    return xplane.ns_per_sample(ctx, PROFILE, ("validate", "score"))
