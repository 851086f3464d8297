"""Batched multi-motif estimation engine (the odeN-style serving path).

Real workloads ask for MANY counts over one graph — every motif of a
family, several ``delta`` windows, progressive sample budgets — and the
sequential ``estimate()`` loop repays none of the shared work: each call
re-uploads the index structure, re-preprocesses every candidate tree and
re-compiles its sampler.  ``estimate_many()`` amortizes all three:

* one ``device_arrays()`` upload serves every job;
* the tree-candidate/preprocess pass is deduplicated through a
  ``(tree_signature, delta, wd, use_c2, backend)`` cache — jobs that
  resolve to the same key (same motif+delta, or distinct motifs whose
  trees share a structural signature) preprocess once and share ONE
  ``Weights`` object;
* sampling runs through the execution engine (core/engine.py): jobs
  sharing a (tree-signature, chunk, Lmax, backend, weights) plan key
  FUSE into a tree-cohort — one shared tree-instance sample stream per
  (seed, chunk), scored against every member motif's own count fn in a
  single vmapped window program per dispatch — and each window's chunk
  range shards over the ``mesh``'s data axes when one is passed.

Per-job outputs are **bit-identical** to ``estimate(g, motif, delta, k,
seed=seed)``: the same candidate ranking picks the same tree, and chunk
``j`` still draws from ``fold_in(PRNGKey(seed), j)`` regardless of which
fused dispatch or mesh shard executes it (engine determinism contract).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Iterable, Sequence

from .. import obs
from .estimator import EstimateResult
from .graph import TemporalGraph
from .motif import TemporalMotif, get_motif
from .spanning_tree import SpanningTree, candidate_trees, tree_signature
from .weights import Weights, depsum_backend, preprocess


@dataclass(frozen=True)
class Job:
    """One estimation request: count ``motif`` under ``delta`` with ``k``
    samples.  ``seed=None`` inherits the batch-level seed."""

    motif: TemporalMotif
    delta: int
    k: int
    seed: int | None = None


def as_job(spec) -> Job:
    """Accept Job | (motif, delta, k[, seed]); motif may be a name."""
    if isinstance(spec, Job):
        return spec
    motif, delta, k, *rest = spec
    if isinstance(motif, str):
        motif = get_motif(motif)
    return Job(motif=motif, delta=int(delta), k=int(k),
               seed=rest[0] if rest else None)


class BatchPlanner:
    """Shared-preprocess tree selection over one graph.

    ``plan(motif, delta)`` mirrors ``estimator.choose_tree`` (same
    candidate order, same strict min-W ranking — so the winning tree is
    identical to the sequential path) but routes every candidate's
    ``preprocess`` through a cache keyed on ``(tree_signature, delta,
    wd, use_c2, backend)`` — structurally-equal trees of different
    motifs share one Weights object (bit-identical DP output).
    """

    def __init__(self, g: TemporalGraph, dev: dict | None = None,
                 n_candidates: int = 3, roots_per_tree: int = 2,
                 use_c2: bool = True, use_c3: bool = True,
                 backend: str | None = None):
        self.g = g
        self.dev = g.device_arrays() if dev is None else dev
        self.n_candidates = n_candidates
        self.roots_per_tree = roots_per_tree
        self.use_c2 = use_c2
        self.use_c3 = use_c3
        self.backend = depsum_backend(backend)
        self._weights: dict = {}
        self._plans: dict = {}
        self.preprocess_calls = 0
        self.preprocess_hits = 0

    def _wd(self, delta: int) -> int:
        return int(delta) if self.use_c3 else int(self.g.time_span) + 1

    def _key(self, tree: SpanningTree, delta: int) -> tuple:
        # keyed on the STRUCTURAL signature, not the tree object: the
        # weight DP reads only signature fields, so trees of *different
        # motifs* sharing a signature resolve to one Weights object —
        # which is exactly the identity the engine's tree-cohort
        # grouping keys on (shared object => shared sample stream)
        return (tree_signature(tree), int(delta), self._wd(delta),
                self.use_c2, self.backend)

    def _preprocess(self, tree: SpanningTree, delta: int) -> Weights:
        return preprocess(self.g, tree, delta, dev=self.dev,
                          use_c2=self.use_c2, use_c3=self.use_c3,
                          backend=self.backend)

    def weights_for(self, tree: SpanningTree, delta: int) -> Weights:
        key = self._key(tree, delta)
        if key in self._weights:
            self.preprocess_hits += 1
        else:
            self.preprocess_calls += 1
            self._weights[key] = self._preprocess(tree, delta)
        return self._weights[key]

    def plan(self, motif: TemporalMotif, delta: int
             ) -> tuple[SpanningTree, Weights]:
        """Min-W tree + its Weights for (motif, delta), cached.

        The uncached candidates preprocess in concurrent threads: each
        is one compile on the host (tens of seconds on a TPU at
        realistic sizes) and one DP on the device.  The compiles run
        side by side; the device still runs the DPs one at a time, so
        peak device memory is that of sequential DPs.  The ranking below
        reads the candidates in order, so the choice is the sequential
        one.  The workers run under the caller's stage and trace
        (``obs.bind``), so their compiles count as ``compile.preprocess``."""
        pkey = (motif, int(delta))
        if pkey in self._plans:
            return self._plans[pkey]
        cands = candidate_trees(motif, n_candidates=self.n_candidates,
                                roots_per_tree=self.roots_per_tree)
        todo: dict = {}
        for tree in cands:
            key = self._key(tree, delta)
            if key not in self._weights:
                todo.setdefault(key, tree)
        run = obs.bind(lambda tree: self._preprocess(tree, delta))
        with ThreadPoolExecutor(max(1, len(todo))) as pool:
            done = dict(zip(todo, pool.map(run, todo.values())))
        self._weights.update(done)
        self.preprocess_calls += len(done)
        self.preprocess_hits += len(cands) - len(done)
        best = None
        for tree in cands:
            w = self._weights[self._key(tree, delta)]
            Wt = int(w.W_total)
            if best is None or Wt < best[0]:
                best = (Wt, tree, w)
        assert best is not None
        self._plans[pkey] = (best[1], best[2])
        return self._plans[pkey]


def estimate_many(g: TemporalGraph, jobs: Iterable, seed: int = 0,
                  chunk: int = 8192, Lmax: int = 16, n_candidates: int = 3,
                  use_c2: bool = True, use_c3: bool = True,
                  checkpoint_every: int = 64, dev: dict | None = None,
                  backend: str | None = None,
                  planner: BatchPlanner | None = None,
                  sampler_backend: str | None = None,
                  mesh=None) -> list[EstimateResult]:
    """Estimate every ``(motif, delta, k)`` job over one shared graph.

    Returns one ``EstimateResult`` per job, in job order, each
    bit-identical to the sequential ``estimate()`` call with the same
    seed.  Pass a ``BatchPlanner`` to carry the preprocess cache across
    calls (a serving loop handling request batches).

    ``backend`` routes weight preprocessing (dep-sums);
    ``sampler_backend`` routes sampling (the fused kernels/tree_sampler
    path when "pallas", per-job fallback as in ``estimate`` — an
    ineligible job splits off into its own xla group without downgrading
    its fused siblings).  ``mesh`` shards every window's chunk range over
    the mesh's data axes.  Jobs sharing a plan key run fused: one
    dispatch covers a whole ``checkpoint_every`` window of ALL of them.

    This is a compatibility shim over the session API (repro.api): the
    whole batch becomes ONE submit window of a one-shot ``Session``
    (``submit_many`` — never split by coalescing limits), bit-identical
    to the pre-session implementation.  Serving loops handling rolling
    request streams should hold a ``Session`` directly.
    """
    from ..api import EstimateConfig, Request, Session
    jobs = [as_job(j) for j in jobs]
    cfg = EstimateConfig(chunk=chunk, Lmax=Lmax,
                         checkpoint_every=checkpoint_every,
                         n_candidates=n_candidates, use_c2=use_c2,
                         use_c3=use_c3, sampler_backend=sampler_backend,
                         depsum_backend=backend, seed=int(seed))
    session = Session(g, cfg, dev=dev, mesh=mesh, planner=planner)
    handles = session.submit_many([
        Request(motif=j.motif, delta=int(j.delta), k=int(j.k),
                seed=int(seed if j.seed is None else j.seed))
        for j in jobs])
    return [h.result() for h in handles]


def sample_matches_many(g: TemporalGraph, specs: Sequence, K: int,
                        seed: int = 0, dev: dict | None = None,
                        planner: BatchPlanner | None = None):
    """Draw ``K`` weighted tree samples + counts per (motif, delta) spec.

    The feature-extraction entry point (examples/motif_features_gnn.py):
    returns per-spec dicts with ``phi_v`` [K, nv], ``cnt2`` [K] and the
    rescale factor ``W/(2K)``, sharing uploads/preprocessing like
    ``estimate_many``.
    """
    import jax

    from .sampler import make_sample_fn
    from .validate import make_count_fn

    if planner is None:
        planner = BatchPlanner(g, dev=dev)
    dev = planner.dev
    fns: dict = {}   # specs resolving to one tree share compiled samplers
    out = []
    for j, spec in enumerate(specs):
        motif, delta = spec[0], int(spec[1])
        if isinstance(motif, str):
            motif = get_motif(motif)
        tree, wts = planner.plan(motif, delta)
        if tree not in fns:
            fns[tree] = (make_sample_fn(tree, K), make_count_fn(tree, K))
        sample_fn, count_fn = fns[tree]
        # spec j draws from fold_in(PRNGKey(seed), j) per the determinism
        # contract — seed-arithmetic keys (PRNGKey(seed + j)) collide
        # across (seed, j) pairs
        s = sample_fn(dev, wts, jax.random.fold_in(jax.random.PRNGKey(seed),
                                                   j))
        c = count_fn(dev, wts, s)
        out.append(dict(motif=motif, tree=tree, phi_v=s["phi_v"],
                        cnt2=c["cnt2"], valid=c["valid"],
                        scale=float(wts.W_total) / (2.0 * K)))
    return out
