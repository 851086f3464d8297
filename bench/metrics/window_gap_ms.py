"""window_gap_ms -- layer: engine dispatch loop (core/engine.py
``run_plan``); source: device_trace; moves: samples_per_s.

Mean device idle, in ms on the profiler clock, between consecutive
executions of the window program whose gap lies inside one
``session.drain`` annotation (``bench/xplane.py``): what the host's
loop between two windows of one request costs the chip.  The gaps
between requests are left out.  None where the capture has no
``session.drain`` annotation around such a gap."""
import os

from bench import xplane

PROFILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out", "profile")


def read(ctx):
    planes = xplane.capture(ctx, PROFILE)
    gaps = xplane.window_gaps_ns(planes) if planes is not None else None
    if not gaps:
        return None
    return sum(gaps) / len(gaps) / 1e6
