"""device_idle_pct.screen -- layer: device (TPU v5e); source:
device_trace; moves: samples_per_s.screen.

1 - (union of device op intervals / captured window), from the
profiler capture of the first engine windows of the screen window: the
formula of ``device_idle_pct.batch``, on the screen cells."""
from bench.trace import idle_pct


def read(ctx):
    return idle_pct(ctx.trace)
