"""window_roofline_pct -- layer: sampler/validator window program
(core/sampler.py, core/validate.py); source: device_trace; moves:
samples_per_s.

The least time the window programs' samples need at the chip's HBM
bandwidth, over the device time of the window programs in the trace.
Bytes: ``bench/workmodel.py``'s lower bound per sample on the served
tree, times the samples of the traced window executions (one execution
draws ``chunk * checkpoint_every`` samples per stream; the batch mix has
one stream).  On a mesh every window runs once on each device, each
drawing its share of the window's samples: the executions, summed over
the devices, are divided by the devices, and the device seconds, summed
likewise, count every chip's bandwidth.  Memory-bound work, so
bandwidth is the roofline."""
from bench.traffic import window_samples


def read(ctx):
    tr, peaks = ctx.trace, ctx.peaks
    if not tr or not peaks:
        return None
    runs = [(n, s) for name, (n, s) in tr["modules"].items()
            if "window" in name]
    n = sum(r[0] for r in runs) / tr["devices"]
    secs = sum(r[1] for r in runs)
    bps = ctx.check["info"]["bytes_per_sample"]
    if not n or secs <= 0 or not bps:
        return None
    per_sample = sum(bps.values()) / len(bps)
    need = n * window_samples(ctx.cell.mix) * per_sample
    return 100.0 * need / peaks["hbm_bytes_per_s"] / secs
