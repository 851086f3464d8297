"""window_allreduce_ms -- layer: mesh combine (core/engine.py
``shard_map`` ``psum``); source: device_trace; moves: samples_per_s.

Mean device time, in ms per window execution per device, of the
innermost all-reduce ops (by HLO opcode: ``all-reduce``,
``all-reduce-start``, ``all-reduce-done``) inside the captured
``*window*`` executions (``bench/xplane.py``): the cross-chip combine
of each window's int64 accumulators, the one part of a window that
exists only across chips.  None where the capture has none, as on one
chip."""
import os

from bench import xplane

PROFILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out", "profile")


def read(ctx):
    planes = xplane.capture(ctx, PROFILE)
    ns = xplane.allreduce_ns(planes) if planes is not None else None
    return None if ns is None else ns / 1e6
