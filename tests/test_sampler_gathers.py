"""Gathers per bisection step in the XLA sampler's jaxpr.

The sampler's device time follows the count of int64 gathers inside its
fixed-trip bisection loops (static ``fori_loop``s lower to ``scan``).  A
two-piece CDF probe ``C(p)`` is one gather from the folded own|prev table;
its offsets are gathered once, before any loop.  So every loop body holds:

- 1 gather: the window search, each child's six bounds, the center's
  ``monotone_find`` and phase B of ``excluded_find`` (or the child's
  ``monotone_find`` without C2);
- 3 gathers: phase A of ``excluded_find`` (``pair_pos``, ``CL``, ``CE``).

A lookup that does not depend on the probe, put back inside a loop, shows
here as a body with more.
"""
from __future__ import annotations

import jax
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core.motif import get_motif
from repro.core.sampler import _make_sample_fn_xla
from repro.core.spanning_tree import candidate_trees
from repro.core.weights import preprocess
from repro.graphs import powerlaw_temporal_graph

LOOPS = ("scan", "while")


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def _gathers(jaxpr, bodies):
    """Gathers of ``jaxpr`` outside any loop; each loop body's own count
    is appended to ``bodies`` (through ``jit``/``pjit`` and other calls)."""
    n = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            n += 1
        for sub in _subjaxprs(eqn):
            if name in LOOPS:
                if name == "while" and sub is eqn.params["cond_jaxpr"].jaxpr:
                    continue
                bodies.append(_gathers(sub, bodies))
            else:
                n += _gathers(sub, bodies)
    return n


@pytest.fixture(scope="module")
def graph():
    return powerlaw_temporal_graph(n=60, m=400, time_span=9_000,
                                   multiplicity=0.6, seed=11)


@pytest.mark.parametrize("use_c2", [True, False])
@pytest.mark.parametrize("motif_name", ["M5-3", "M4-2", "M4-3"])
def test_one_gather_per_cdf_probe(graph, motif_name, use_c2):
    tree = candidate_trees(get_motif(motif_name), n_candidates=1,
                           roots_per_tree=1)[0]
    dev = graph.device_arrays()
    wts = preprocess(graph, tree, 3_000, dev=dev, use_c2=use_c2)
    fn = _make_sample_fn_xla(tree, 64)
    closed = jax.make_jaxpr(fn)(dev, wts, jax.random.PRNGKey(0))
    bodies = []
    _gathers(closed.jaxpr, bodies)

    children = tree.num_edges - 1
    # window + center, then per child: 3 CSR bounds and the inverse CDF,
    # or with C2 3 pair-list bounds more and the CDF in two phases
    ones = 2 + children * (3 + (4 if use_c2 else 1))
    threes = children if use_c2 else 0
    assert sorted(bodies) == [1] * ones + [3] * threes, (
        f"{motif_name} use_c2={use_c2}: gathers per loop body {bodies}")
