"""A lower bound on the device bytes one sample needs.

Counted per sample at the widths the data needs (a vertex id in
``bits(n-1)`` bits, a time in ``bits(span)``, a weight-prefix entry in
``bits(W)``), whatever widths the program uses, so that a change of
representation cannot move it:

* every sampled edge's record: source, destination and time;
* one weight-prefix entry per bisection step over each candidate
  segment: the window table, the root's window, each child's range;
* the validator's candidate lists: each non-tree edge's in-range times.

The segment lengths are those of the reference's own draws on the same
rooted tree (``reference.Draw.spans``), a function of tree, graph and
samples alone.
"""
from __future__ import annotations

import math

import numpy as np


def bits(x: int) -> int:
    return max(1, math.ceil(math.log2(int(x) + 1)))


def _steps(lengths) -> float:
    """Mean bisection steps over segments of the given lengths."""
    lengths = np.asarray(lengths, np.float64)
    return float(np.ceil(np.log2(lengths + 1)).mean()) if lengths.size else 0


def bytes_per_sample(draw, g, tree) -> float:
    v, t, w = bits(g.n - 1), bits(g.span), bits(max(draw.W, 1))
    sp = draw.spans
    record = len(tree.edges) * (2 * v + t)
    prefix = (bits(sp["windows"]) + _steps(sp["root"])
              + sum(_steps(c) for c in sp["child"])) * w
    listed = sum(float(np.mean(c)) for c in sp["listed"]) * t
    return (record + prefix + listed) / 8.0
