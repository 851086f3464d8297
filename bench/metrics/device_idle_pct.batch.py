"""device_idle_pct.batch -- layer: device (TPU v5e); source:
device_trace; moves: samples_per_s.

1 - (union of device op intervals / captured window), from the
profiler capture of the first engine windows of the batch window."""


def read(ctx):
    tr = ctx.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
