"""Sharded cross-job execution engine: one mesh-wide dispatch per window.

This module owns ALL estimator dispatch (design note — the ROADMAP
"Multi-device sharded sampling" + "Cross-job fusion" items land here).

Why an engine layer
-------------------
TIMEST's estimator is embarrassingly parallel across samples (paper
Alg. 6/7): chunk ``j`` of a job is a pure function of
``fold_in(PRNGKey(seed), j)`` and reduces to six int64 scalars.  Real
workloads (odeN-style multi-motif serving) run MANY such jobs over one
graph, and the wins live in aggregating their dispatches:

* **Tree-cohort fusion (shared-sample multi-motif)** — jobs whose trees
  share a *structural signature* (``spanning_tree.tree_signature``) are
  grouped into one cohort: the tree-instance stream is drawn ONCE per
  distinct (seed) stream — base keys stack into ``[J_streams, 2]`` and
  ``core.sampler.make_batched_sample_fn`` runs over the cohort's LEAD
  tree — and every member motif scores each sample through its own
  count fn on a second ``[M_lanes]`` axis
  (``core.sampler.make_cohort_count_fn``).  N standing queries on one
  tree cost ~1 sampling pass instead of N (the odeN-style fan-out win).
* **Mesh sharding** — the chunk range of each window is ``shard_map``-ed
  over the mesh's data axes (``dist.sharding.data_axes``): shard ``d`` of
  ``D`` executes chunk offsets ``d, d + D, d + 2D, ...`` (round-robin by
  the static stride ``D``) and one ``jax.lax.psum`` combines the int64
  accumulator dicts.

A ``checkpoint_every`` window of a J-stream/M-lane cohort on D devices
is therefore ONE dispatch instead of (J x M) x window host round-trips.

The plan key
------------
Jobs fuse when they share ``(tree_signature, chunk, Lmax, backend)``
*and* the same ``Weights`` object (same preprocess output — the batch
planner keys its cache on the signature too, so distinct motifs whose
trees are structurally equal share one Weights object and land in one
cohort; jobs differing only in ``k``/``seed`` fuse as before).  Within
a group, distinct trees become *lanes* (one count fn each) and distinct
seeds become *streams* (one sample row each); job (seed, tree) reads
cell ``[stream, lane]`` of the window sums.  The compiled window
program is memoized in a bounded LRU keyed on the full plan key
``(lane trees, chunk, Lmax, backend, mesh)`` — distinct graphs/Lmax
variants age out instead of accumulating forever.  ``backend`` is
resolved PER JOB before grouping: a ``pallas_sampler_eligible`` veto
downgrades only that job to "xla" (recorded as
``EstimateResult.fallback_reason``) and the group splits, instead of
dragging every fused sibling down.

Sharing is sound because the samplers (both backends) and the weight DP
read only signature fields — never ``edge_ids`` or non-tree edges — so
signature-equal trees induce bit-identical Alg. 3 instance streams,
while validation/DeriveCnt stay lane-local: each motif's accept/reject
derives from the shared sample and its own spec alone.  The per-motif
unbiasing correction is each lane's own ``W``/``cnt2`` in
``estimator.unbias_estimate``.

Determinism contract
--------------------
Results are **bit-identical** to sequential ``estimate()`` on ANY mesh
shape, fused or not:

* chunk ``j`` always draws from ``fold_in(base_key, j)`` — the chunk ->
  key map never depends on which shard executes it, on the job axis, or
  on the motif lane (a cohort's stream must never fold a motif index
  into a sampling key — lint rule ``det-cohort-key``), so a job's
  results are bit-identical regardless of which other motifs joined its
  cohort;
* accumulators are exact int64 sums of per-chunk int64 scalars, and
  integer addition is associative + commutative, so the shard-local scan
  order and the psum combine order cannot change the total;
* window grids align to ``checkpoint_every`` boundaries, so a checkpoint
  written on a 1-device run resumes bit-identically on an 8-device mesh
  (and vice versa) — the checkpoint stores only ``(chunks_done, acc)``,
  which is mesh-shape-free.

Shards execute ``ceil(n / D)`` slots each; offsets past ``n`` are masked
to zero contribution (the chunk is computed and discarded — SPMD padding,
never a collective divergence).
"""
from __future__ import annotations

import json
import os
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any

from .. import obs
from ..knobs import get_knob
from ..resilience import STATS as RSTATS
from ..resilience import atomic_write_json, classify, fire, is_retryable
from ..resilience.retry import DISPATCH_POLICY, backoff_delay
from ..util import ensure_x64

ensure_x64()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from ..dist.collectives import folded_axis_index  # noqa: E402
from ..dist.sharding import data_axes, n_data  # noqa: E402
from .estimator import _ACC_KEYS, EstimateResult, unbias_estimate  # noqa: E402
from .motif import TemporalMotif  # noqa: E402
from .sampler import (WITNESS_SENTINEL, make_batched_sample_fn,  # noqa: E402
                      make_cohort_count_fn, make_witness_fn)  # noqa: E402
from .sampler import sampler_backend as _resolve_backend  # noqa: E402
from .spanning_tree import SpanningTree, tree_signature  # noqa: E402
from .weights import Weights  # noqa: E402


# ---------------------------------------------------------------------------
# compiled window programs: fused over jobs, sharded over chunks
# ---------------------------------------------------------------------------
def _as_lanes(trees) -> tuple:
    """Normalize a single tree or an iterable of lane trees to a tuple."""
    if isinstance(trees, SpanningTree):
        return (trees,)
    return tuple(trees)


def make_engine_window_fn(trees, chunk: int, Lmax: int = 16,
                          backend: str | None = None, mesh=None):
    """``fn(dev, wts, base_keys, j0, n) -> {key: [J, M] int64}``: chunks
    ``j0 .. j0+n-1`` of a J-stream, M-lane tree-cohort in ONE dispatch.

    ``trees`` is one ``SpanningTree`` or a tuple of signature-equal lane
    trees (one per member motif; the lead tree drives sampling).
    ``base_keys [J, 2]`` stacks the cohort's distinct seed streams;
    chunk ``j`` of stream ``i`` draws from ``fold_in(base_keys[i], j)``
    exactly as the sequential path does — never from a lane index — and
    every lane's count fn scores the SAME ``[J]`` sample batch
    (``make_cohort_count_fn``), so cell ``[i, l]`` is bit-identical to a
    solo run of lane ``l``'s motif at stream ``i``'s seed.  ``n`` is
    static (one compile per distinct window length); ``j0`` is traced,
    so resuming mid-stream never recompiles.  With a ``mesh``, the body
    runs under ``shard_map`` over the data axes: shard ``d`` scans
    offsets ``d + i*D`` (static stride round-robin), masks offsets past
    ``n``, and a ``psum`` combines the exact int64 accumulators.
    """
    lanes = _as_lanes(trees)
    bs_fn = make_batched_sample_fn(lanes[0], chunk, backend=backend)
    cc_fn = make_cohort_count_fn(lanes, chunk, Lmax=Lmax, keys=_ACC_KEYS)
    M = len(lanes)

    def chunk_sums(dev, wts, base_keys, j):
        with jax.named_scope("sample"):
            keys = jax.vmap(lambda bk: jax.random.fold_in(bk, j))(base_keys)
        return cc_fn(dev, wts, bs_fn(dev, wts, keys))

    if mesh is not None and (not data_axes(mesh)
                             or n_data(mesh) != mesh.size):
        raise ValueError(
            f"engine meshes must be data-only (axes {mesh.axis_names}, "
            f"data extent {n_data(mesh)} of {mesh.size} devices): chunks "
            "round-robin over data_axes and any other axis would "
            "recompute every chunk per shard — build one with "
            "launch.mesh.make_estimator_mesh")

    if mesh is None:
        def window(dev, wts, base_keys, j0, n):
            def step(acc, j):
                out = chunk_sums(dev, wts, base_keys, j)
                with jax.named_scope("score"):
                    return {k: acc[k] + out[k] for k in _ACC_KEYS}, None

            acc0 = {k: jnp.zeros((base_keys.shape[0], M), jnp.int64)
                    for k in _ACC_KEYS}
            acc, _ = jax.lax.scan(step, acc0, j0 + jnp.arange(n))
            return acc

        return jax.jit(window, static_argnames=("n",))

    axes = data_axes(mesh)
    D = n_data(mesh)

    def window(dev, wts, base_keys, j0, n):
        slots = -(-n // D)

        def body(dev, wts, base_keys, j0):
            d = folded_axis_index(mesh, axes)

            def step(acc, i):
                off = d + i * D
                out = chunk_sums(dev, wts, base_keys, j0 + off)
                with jax.named_scope("score"):
                    live = (off < n).astype(jnp.int64)
                    return {k: acc[k] + out[k] * live
                            for k in _ACC_KEYS}, None

            acc0 = {k: jnp.zeros((base_keys.shape[0], M), jnp.int64)
                    for k in _ACC_KEYS}
            acc, _ = jax.lax.scan(step, acc0, jnp.arange(slots))
            return jax.lax.psum(acc, axes)

        sm = jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P(), P()),
                           out_specs=P(), check_vma=False)
        return sm(dev, wts, base_keys, j0)

    return jax.jit(window, static_argnames=("n",))


# ---------------------------------------------------------------------------
# witness window programs (deterministic reservoir over accepted matches)
# ---------------------------------------------------------------------------
_WIT_KEYS = ("prio", "eids", "src", "dst", "t", "cnt2")


def _witness_width(n: int) -> int:
    """Pad the compiled reservoir width to a power of two (floor 4) so
    nearby ``witnesses=`` values share one compiled program; the host
    trims back to the requested count."""
    return max(4, 1 << (int(n) - 1).bit_length())


def make_witness_window_fn(tree, chunk: int, Lmax: int = 16,
                           n_wit: int = 8, backend: str | None = None):
    """``fn(dev, wts, base_key, j0, n, seed) -> dict``: scan chunks
    ``j0 .. j0+n-1`` merging each chunk's witness reservoir
    (``sampler.make_witness_fn``) into the window's top-``n_wit``.

    Chunk ``j`` re-draws from ``fold_in(base_key, j)`` — the exact keys
    the counting path used — so witnesses come from the same instance
    stream the estimate counted.  Always runs UNSHARDED, on any mesh:
    the reservoir merge is a pure function of the (seed, chunk)
    priorities and the fixed chunk order, so the window's top-``n_wit``
    is bit-identical across mesh shapes by construction (witness
    dispatches move ``n_wit`` rows, not windows of samples — sharding
    them would buy nothing).  ``seed`` is traced, so one compiled
    program serves every job/tenant sharing ``(tree, chunk, Lmax,
    n_wit, backend)``.
    """
    w_fn = make_witness_fn(tree, chunk, Lmax=Lmax, n_wit=n_wit,
                           backend=backend)
    S = tree.num_edges

    def window(dev, wts, base_key, j0, n, seed):
        def step(carry, j):
            out = w_fn(dev, wts, jax.random.fold_in(base_key, j), j, seed)
            prio = jnp.concatenate([carry["prio"], out["prio"]])
            order = jnp.argsort(prio)[:n_wit]
            merged = {kk: jnp.concatenate([carry[kk], out[kk]])[order]
                      for kk in _WIT_KEYS}
            return merged, None

        init = dict(
            prio=jnp.full((n_wit,), WITNESS_SENTINEL, jnp.int64),
            eids=jnp.zeros((n_wit, S), jnp.int64),
            src=jnp.zeros((n_wit, S), jnp.int64),
            dst=jnp.zeros((n_wit, S), jnp.int64),
            t=jnp.zeros((n_wit, S), jnp.int64),
            cnt2=jnp.zeros((n_wit,), jnp.int64))
        carry, _ = jax.lax.scan(step, init, j0 + jnp.arange(n))
        return carry

    return jax.jit(window, static_argnames=("n",))


# ---------------------------------------------------------------------------
# bounded LRU over compiled window programs (full plan key)
# ---------------------------------------------------------------------------
_WINDOW_FN_LRU: OrderedDict = OrderedDict()

# registry-backed LRU accounting: monotonic across clear_window_cache()
# (the cache clears; the counters never do — scrape deltas stay meaningful)
_LRU_EVENTS = obs.REGISTRY.counter(
    "repro_engine_window_lru_total",
    "compiled window-program LRU lookups by cache and event",
    labels=("cache", "event"))
_LRU_WINDOW_HIT = _LRU_EVENTS.labels(cache="window", event="hit")
_LRU_WINDOW_MISS = _LRU_EVENTS.labels(cache="window", event="miss")
_LRU_WITNESS_HIT = _LRU_EVENTS.labels(cache="witness", event="hit")
_LRU_WITNESS_MISS = _LRU_EVENTS.labels(cache="witness", event="miss")

def _cache_capacity() -> int:
    return max(1, get_knob("REPRO_ENGINE_CACHE"))


def cached_window_fn(trees, chunk: int, Lmax: int = 16,
                     backend: str | None = None, mesh=None):
    """LRU-memoized ``make_engine_window_fn`` keyed on the FULL plan key
    ``(lane trees, chunk, Lmax, backend, mesh)`` — ``trees`` is a single
    tree or the cohort's lane-tree tuple.

    Bounded at ``REPRO_ENGINE_CACHE`` entries (default 32): evicting an
    entry drops its jit function, so programs for long-gone graphs/Lmax
    variants are garbage-collected instead of accumulating across a
    serving process's lifetime.
    """
    lanes = _as_lanes(trees)
    key = (lanes, int(chunk), int(Lmax), _resolve_backend(backend), mesh)
    fn = _WINDOW_FN_LRU.get(key)
    if fn is None:
        _LRU_WINDOW_MISS.inc()
        fn = make_engine_window_fn(lanes, chunk, Lmax=Lmax, backend=key[3],
                                   mesh=mesh)
        _WINDOW_FN_LRU[key] = fn
    else:
        _LRU_WINDOW_HIT.inc()
    _WINDOW_FN_LRU.move_to_end(key)
    while len(_WINDOW_FN_LRU) > _cache_capacity():
        _WINDOW_FN_LRU.popitem(last=False)
    return fn


def cached_witness_fn(tree, chunk: int, Lmax: int = 16, n_wit: int = 8,
                      backend: str | None = None):
    """LRU-memoized ``make_witness_window_fn`` sharing ``_WINDOW_FN_LRU``
    — the key's lane slot carries a ``"witness"`` marker plus the padded
    reservoir width, so witness programs age with the count programs and
    the ``no_retrace`` sentinel watches them for free."""
    key = ((tree, "witness", int(n_wit)), int(chunk), int(Lmax),
           _resolve_backend(backend), None)
    fn = _WINDOW_FN_LRU.get(key)
    if fn is None:
        _LRU_WITNESS_MISS.inc()
        fn = make_witness_window_fn(tree, chunk, Lmax=Lmax, n_wit=n_wit,
                                    backend=key[3])
        _WINDOW_FN_LRU[key] = fn
    else:
        _LRU_WITNESS_HIT.inc()
    _WINDOW_FN_LRU.move_to_end(key)
    while len(_WINDOW_FN_LRU) > _cache_capacity():
        _WINDOW_FN_LRU.popitem(last=False)
    return fn


def clear_window_cache() -> None:
    """Drop every cached window program (tests/benchmark cold starts).

    Clears the CACHE only: the registry-backed counters (``STATS``,
    LRU hit/miss) are monotonic and survive — scrapers never see a
    counter move backwards because a test dropped compiled programs."""
    _WINDOW_FN_LRU.clear()


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class PlanKey:
    """Fusion key: jobs sharing it (plus Weights identity) form one
    tree-cohort and run through one compiled program."""

    signature: tuple  # spanning_tree.tree_signature of every member tree
    chunk: int
    Lmax: int
    backend: str     # resolved sampler backend ("xla" | "pallas")


@dataclass
class EngineJob:
    """One planned estimation job + its runtime cursor/accumulators."""

    index: int
    motif: TemporalMotif
    delta: int
    k: int
    seed: int
    tree: SpanningTree
    wts: Weights
    checkpoint_path: str | None = None
    # in-memory resume ``(chunks_done, acc)``: the session layer's
    # adaptive-budget growth rounds continue a job from its previous
    # round's cursor instead of re-reading (or needing) a checkpoint
    # file.  Takes precedence over ``checkpoint_path`` when set.
    resume: tuple | None = None
    # absolute ``time.monotonic()`` deadline: when it passes mid-run the
    # job stops at its last completed checkpoint window and returns a
    # partial result marked ``degraded`` (never an error)
    deadline_t: float | None = None
    # witness capture: keep up to this many accepted full-match edge
    # tuples (deterministic reservoir, ``sampler.witness_priority``).
    # 0 = no witness dispatch at all (the count path never pays for it).
    witnesses: int = 0
    # merged witness reservoir, keyed by the edge-id tuple: the same
    # match sampled in several chunks collapses to its best priority
    wit: dict = field(default_factory=dict)
    # resolved by plan_jobs
    backend: str = "xla"
    fallback_reason: str = ""
    degraded: bool = False
    degrade_reason: str = ""
    # runtime degradation ladder state: 0 = dispatch whole windows; a
    # positive value caps the chunks per compiled dispatch (execution
    # only — the chunk -> fold_in key map and the checkpoint grid are
    # untouched, so halved windows stay bit-identical)
    max_window: int = 0
    n_chunks: int = 0
    k_eff: int = 0
    cursor: int = 0
    acc: dict = field(default_factory=dict)
    base_key: Any = None
    group_size: int = 1
    # tree-cohort coordinates, resolved by plan_jobs: the job reads cell
    # ``[stream(seed), lane]`` of its cohort's window sums
    lane: int = 0
    # obs trace id of the request that planned this job (None when the
    # caller runs untraced); dispatch spans report it so a request's
    # flight-recorder chain reaches the engine
    trace: str | None = None
    # timings (tree_select_s/preprocess_s are filled by the front-ends)
    sampling_s: float = 0.0
    preprocess_s: float = 0.0
    tree_select_s: float = 0.0


@dataclass
class JobGroup:
    key: PlanKey
    wts: Weights
    jobs: list
    # deduped lane trees (first-seen job order; one count fn each) and
    # the deduped seed-stream width the cohort key stacks pad to
    lane_trees: tuple = ()
    n_streams: int = 1


@dataclass
class ExecutionPlan:
    """Grouped jobs + the mesh/window config ``run_plan`` executes."""

    jobs: list          # input order
    groups: list
    dev: dict
    mesh: Any
    chunk: int
    Lmax: int
    checkpoint_every: int
    dispatches: int = 0

    @property
    def mesh_shape(self) -> tuple | None:
        if self.mesh is None:
            return None
        return tuple(int(self.mesh.shape[a]) for a in self.mesh.axis_names)


class EngineStats(obs.CounterBlock):
    """Process-wide dispatch accounting (tests assert on these) — a
    registry-backed :class:`repro.obs.registry.CounterBlock` facade.
    The attribute API is unchanged (``STATS.dispatches += 1`` etc.) but
    each field is a monotonic registry counter
    (``repro_engine_*_total``) that also appears in the
    ``{"cmd": "metrics"}`` Prometheus scrape and survives
    ``clear_window_cache()``; ``reset()`` is a test-only seam.

    ``dispatches``          compiled window programs launched
    ``fused_dispatches``    dispatches carrying more than one job
    ``job_windows``         job x window pairs covered
    ``tree_cohorts``        cohort windows dispatched
    ``cohort_motif_lanes``  distinct motif lanes over those windows
    ``samples_shared``      samples consumed without being redrawn
    ``witness_dispatches``  witness reservoir windows dispatched
    ``samples_drawn``       samples drawn (chunk x chunks x distinct
                            streams per cohort window; take its rate)
    """

    _PREFIX = "repro_engine"
    _FIELDS = ("dispatches", "fused_dispatches", "job_windows",
               "tree_cohorts", "cohort_motif_lanes", "samples_shared",
               "witness_dispatches", "samples_drawn")
    _DOCS = {
        "dispatches": "compiled window programs launched",
        "fused_dispatches": "dispatches carrying more than one job",
        "job_windows": "job x window pairs covered",
        "tree_cohorts": "cohort windows dispatched",
        "cohort_motif_lanes": "distinct motif lanes over cohort windows",
        "samples_shared": "samples consumed without being redrawn",
        "witness_dispatches": "witness reservoir windows dispatched",
        "samples_drawn": "samples drawn by cohort window dispatches",
    }

    @property
    def motifs_per_cohort(self) -> float:
        """Mean motif-lane fan-out per cohort window (1.0 = no sharing)."""
        if not self.tree_cohorts:
            return 0.0
        return self.cohort_motif_lanes / self.tree_cohorts


STATS = EngineStats()


def _load_checkpoint(job: EngineJob, chunk: int) -> None:
    """Resume ``(cursor, acc)`` from the job's checkpoint when it matches.

    The format (and the match predicate) is exactly the sequential
    estimator's, and records nothing about the mesh — which is what makes
    resume bit-identical across mesh shapes.

    A torn or corrupt checkpoint (a crash predating the atomic-write
    path, or external truncation) is treated as absent: the job starts
    fresh instead of poisoning the run.
    """
    path = job.checkpoint_path
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as f:
            st = json.load(f)
    except (OSError, ValueError):
        return                      # torn/unreadable: start fresh
    if not isinstance(st, dict) or not all(
            kk in st for kk in ("motif", "delta", "seed", "chunk",
                                "tree_edges", "chunks_done", "acc")):
        return
    if (st["motif"] == job.motif.name and st["delta"] == job.delta
            and st["seed"] == job.seed and st["chunk"] == chunk
            and tuple(st["tree_edges"]) == job.tree.edge_ids
            # a checkpoint from a LARGER budget would divide its counts
            # by this run's smaller k — stale state, start fresh
            and int(st["chunks_done"]) <= job.n_chunks):
        job.acc = {kk: int(st["acc"][kk]) for kk in _ACC_KEYS}
        job.cursor = int(st["chunks_done"])


def _write_checkpoint(job: EngineJob, chunk: int) -> None:
    # atomic (temp + os.replace, via the resilience layer): a crash mid-
    # write leaves the previous complete checkpoint, never a torn one
    atomic_write_json(
        job.checkpoint_path,
        dict(motif=job.motif.name, delta=job.delta, seed=job.seed,
             chunk=chunk, tree_edges=list(job.tree.edge_ids),
             chunks_done=job.cursor, acc=job.acc))


def plan_jobs(jobs, *, dev: dict, chunk: int = 8192, Lmax: int = 16,
              checkpoint_every: int = 64, mesh=None,
              sampler_backend: str | None = None) -> ExecutionPlan:
    """Resolve backends, load checkpoints and group jobs into a plan.

    ``jobs`` is a list of ``EngineJob``s with identity fields set (index,
    motif, delta, k, seed, tree, wts, checkpoint_path).  The requested
    ``sampler_backend`` is resolved per job: pallas-ineligible jobs are
    downgraded to "xla" individually (reason recorded), which splits
    their fused group instead of downgrading every job in it.

    Jobs group into tree-cohorts keyed by ``(tree_signature, chunk,
    Lmax, backend)`` + Weights identity: within a group, distinct trees
    become count-fn *lanes* and distinct seeds become sample *streams*
    (``job.lane`` records the job's lane; its stream row is resolved
    per-cohort at dispatch).  Distinct motifs land in one cohort exactly
    when the batch planner resolved them to one shared Weights object
    (signature-keyed preprocess cache).
    """
    sb_req = _resolve_backend(sampler_backend)
    elig: dict[int, tuple[bool, str]] = {}
    groups: OrderedDict = OrderedDict()
    for job in jobs:
        job.backend, job.fallback_reason = sb_req, ""
        if sb_req == "pallas":
            wid = id(job.wts)
            if wid not in elig:
                from ..kernels.tree_sampler.ops import pallas_sampler_eligible
                elig[wid] = pallas_sampler_eligible(dev, job.wts)
            ok, why = elig[wid]
            if not ok:
                job.backend, job.fallback_reason = "xla", why
        job.n_chunks = max(1, -(-job.k // chunk))
        job.k_eff = job.n_chunks * chunk
        job.cursor = 0
        job.acc = {kk: 0 for kk in _ACC_KEYS}
        job.base_key = jax.random.PRNGKey(job.seed)
        if int(job.wts.W_total) == 0:
            job.cursor = job.n_chunks       # nothing to sample
        elif job.resume is not None:
            done, acc = job.resume
            if 0 <= int(done) <= job.n_chunks:
                job.cursor = int(done)
                job.acc = {kk: int(acc[kk]) for kk in _ACC_KEYS}
        else:
            _load_checkpoint(job, chunk)
        gkey = (PlanKey(tree_signature(job.tree), int(chunk), int(Lmax),
                        job.backend),
                id(job.wts))
        if gkey not in groups:
            groups[gkey] = JobGroup(key=gkey[0], wts=job.wts, jobs=[])
        groups[gkey].jobs.append(job)
    for group in groups.values():
        lanes: dict = {}      # tree -> lane index (first-seen job order)
        seeds: set = set()
        for job in group.jobs:
            job.group_size = len(group.jobs)
            job.lane = lanes.setdefault(job.tree, len(lanes))
            seeds.add(job.seed)
        group.lane_trees = tuple(lanes)
        group.n_streams = len(seeds)
    return ExecutionPlan(jobs=list(jobs), groups=list(groups.values()),
                         dev=dev, mesh=mesh, chunk=int(chunk),
                         Lmax=int(Lmax),
                         checkpoint_every=max(1, int(checkpoint_every)))


def _attempt_dispatch(window_fn, plan, wts, base_keys, j0, n, backend):
    """One window dispatch with the transient-retry loop.

    Retries ``classify() == retryable`` failures up to the policy's
    attempt budget with deterministically-jittered backoff (the jitter
    seed is the dispatch's own ``j0`` — replayable, yet distinct shards
    de-synchronize).  Non-retryable failures and exhausted budgets raise
    to the caller (the ladder).
    """
    last: Exception | None = None
    for attempt in range(DISPATCH_POLICY.max_attempts):
        try:
            fire("engine.dispatch", tag=backend)
            with obs.span("engine.device", stage="device",
                          backend=backend, j0=int(j0), n=int(n)):
                sums = window_fn(plan.dev, wts, base_keys, j0, n)
                # materialize inside the try: device faults surface here
                sums = {kk: np.asarray(sums[kk]) for kk in _ACC_KEYS}
            return sums
        except Exception as e:
            if not is_retryable(e):
                raise
            last = e
            RSTATS.retries += 1
            if attempt < DISPATCH_POLICY.max_attempts - 1:
                time.sleep(backoff_delay(DISPATCH_POLICY, attempt,
                                         seed=int(j0)))
    assert last is not None
    raise last


def _run_cohort_window(plan, group, get_fn, cjobs, base_keys, j0, n):
    """Dispatch one cohort window through the degradation ladder.

    Rungs, taken only after the retry budget at the current rung is
    exhausted on a *retryable* failure:

    1. current backend, whole window;
    2. ``pallas -> xla`` backend swap (only the cohort's jobs degrade —
       fused siblings in other cohorts keep their backend);
    3. dispatch-window halving: the ``checkpoint_every`` window is
       sub-dispatched in spans of ``max_window`` chunks, host-summed
       (exact int64).  Purely an execution change — chunk ``j`` still
       draws ``fold_in(base_key, j)`` and the checkpoint grid is
       untouched, so every rung stays bit-identical.

    When the window cannot shrink further the last error raises (fatal).
    Returns ``(sums, n_dispatches)`` and records the rung taken on the
    cohort's jobs (``backend`` / ``max_window`` / ``fallback_reason``).
    """
    backend = cjobs[0].backend
    max_window = cjobs[0].max_window
    while True:
        try:
            window_fn = get_fn(backend)
            if not max_window or max_window >= n:
                return _attempt_dispatch(window_fn, plan, group.wts,
                                         base_keys, j0, n, backend), 1
            total: dict | None = None
            parts = 0
            done = 0
            while done < n:
                step = min(max_window, n - done)
                part = _attempt_dispatch(window_fn, plan, group.wts,
                                         base_keys, j0 + done, step, backend)
                parts += 1
                total = part if total is None else {
                    kk: total[kk] + part[kk] for kk in _ACC_KEYS}
                done += step
            return total, parts
        except Exception as e:
            if not is_retryable(e):
                raise
            if backend == "pallas":
                backend = "xla"
                reason = "ladder: pallas -> xla after repeated transient " \
                         "dispatch failure"
            else:
                cur = max_window if max_window and max_window < n else n
                if cur <= 1:
                    raise           # smallest dispatch still failing
                max_window = cur // 2
                reason = f"ladder: dispatch window halved to {max_window} " \
                         "chunks after repeated transient failure"
            RSTATS.ladder_steps += 1
            for job in cjobs:
                job.backend = backend
                job.max_window = max_window
                job.fallback_reason = (job.fallback_reason + "; " + reason
                                       if job.fallback_reason else reason)


def _run_witness_window(plan, group, job, j0, n) -> None:
    """Dispatch one job's witness reservoir for a completed window and
    merge the device top-``n_wit`` into ``job.wit``.

    Guarded by ``job.witnesses > 0`` at the call site — a plain count
    job never dispatches (or compiles) a witness program.  Transient
    failures retry like count dispatches; ``job.wit`` is keyed by the
    edge-id tuple at its best (smallest) priority, and is never trimmed
    here — keeping every per-window survivor makes the merged reservoir
    an exact union of per-window device tops, so an adaptive run split
    into resume rounds merges to the same set as one uninterrupted run
    at the final budget.
    """
    width = _witness_width(job.witnesses)
    fn = cached_witness_fn(job.tree, plan.chunk, Lmax=plan.Lmax,
                           n_wit=width, backend=job.backend)
    last: Exception | None = None
    for attempt in range(DISPATCH_POLICY.max_attempts):
        try:
            fire("engine.witness", tag=job.backend)
            out = fn(plan.dev, group.wts, job.base_key, j0, n, job.seed)
            out = {kk: np.asarray(out[kk]) for kk in _WIT_KEYS}
            last = None
            break
        except Exception as e:
            if not is_retryable(e):
                raise
            last = e
            RSTATS.retries += 1
            if attempt < DISPATCH_POLICY.max_attempts - 1:
                time.sleep(backoff_delay(DISPATCH_POLICY, attempt,
                                         seed=int(j0)))
    if last is not None:
        raise last
    STATS.witness_dispatches += 1
    # present edges in motif (pi) order, not tree-local order
    rank_order = sorted(range(job.tree.num_edges),
                        key=lambda s: job.tree.edge_ids[s])
    for i in range(width):
        p = int(out["prio"][i])
        if p >= WITNESS_SENTINEL:
            break                      # sorted: the rest are padding
        eid_row = tuple(int(x) for x in out["eids"][i])
        cur = job.wit.get(eid_row)
        if cur is None or p < cur["prio"]:
            job.wit[eid_row] = dict(
                prio=p, cnt=int(out["cnt2"][i]),
                edges=tuple((int(out["src"][i][s]), int(out["dst"][i][s]),
                             int(out["t"][i][s])) for s in rank_order))


def witness_entries(wit: dict, n: int) -> tuple:
    """Format a merged witness reservoir as the public payload: up to
    ``n`` entries ordered by reservoir priority, each
    ``{"edges": ((src, dst, t), ...), "cnt": ..., "prio": ...}`` with
    the tree's edges in motif (pi) order.  JSON-safe (tuples encode as
    arrays) — the serving layers emit these dicts verbatim."""
    top = sorted(wit.values(), key=lambda e: e["prio"])[:max(0, int(n))]
    return tuple(dict(edges=e["edges"], cnt=e["cnt"], prio=e["prio"])
                 for e in top)


def _mark_deadline_expired(jobs, chunk) -> list:
    """Split off jobs whose deadline has passed; they stop at their last
    completed checkpoint window (cursor stays put).  Returns survivors."""
    now = obs.monotonic()
    live = []
    for job in jobs:
        if job.deadline_t is not None and now >= job.deadline_t:
            job.degraded = True
            job.degrade_reason = (
                f"deadline: stopped at k={job.cursor * chunk} "
                f"of {job.k_eff} (last completed checkpoint window)")
            RSTATS.deadline_degraded += 1
        else:
            live.append(job)
    return live


def run_plan(plan: ExecutionPlan, on_window=None) -> list[EstimateResult]:
    """Execute a plan: one dispatch per (job-cohort, window); results in
    input job order, bit-identical to sequential ``estimate()``.

    ``on_window(job, window_sums, j0, n)`` fires once per job per
    completed window, after the job's accumulators and cursor have
    advanced — the session layer's hook for progressive streaming and
    batch-means RSE (``window_sums`` is THIS window's int sums dict).

    Within a group, jobs whose next window coincides — same ``(j0, n)``
    on the ``checkpoint_every``-aligned grid — form a cohort and dispatch
    together; fresh same-budget jobs stay fused for their whole run,
    resumed or short-budget jobs peel off into their own cohorts without
    perturbing anyone's chunk -> key map.  A cohort's key stack holds one
    row per DISTINCT seed (jobs sharing a seed read the same sample
    stream — ``STATS.samples_shared`` counts what they did not redraw)
    and is padded to the group's stream width, so the compiled program
    sees one stable ``[J, 2]`` shape across the group's whole drain (no
    retrace when a short-budget job finishes — on real hardware a window
    recompile costs far more than the padded rows, which replay the lead
    stream's keys and have their sums discarded).  Each job reads cell
    ``[stream(seed), lane(tree)]`` of the ``[J, M]`` window sums.  Fused
    jobs report the shared dispatch wall-clock as their ``sampling_s``.

    Resilience (see ``repro.resilience``): every dispatch runs through a
    transient-retry loop and, on persistent failure, the per-cohort
    degradation ladder (``_run_cohort_window``) — degraded jobs record
    the rung in ``fallback_reason`` and keep bit-identical results.
    Jobs whose ``deadline_t`` passes stop at their last completed
    checkpoint window and return partials marked ``degraded`` with the
    samples actually drawn as ``k`` (never an error).
    """
    ce = plan.checkpoint_every
    for group in plan.groups:
        fns = {}

        def get_fn(backend, _group=group):
            fn = fns.get(backend)
            if fn is None:
                fire("sampler.call", tag=backend)
                fn = cached_window_fn(_group.lane_trees, _group.key.chunk,
                                      Lmax=_group.key.Lmax, backend=backend,
                                      mesh=plan.mesh)
                fns[backend] = fn
            return fn

        active = [j for j in group.jobs if j.cursor < j.n_chunks]
        while active:
            active = _mark_deadline_expired(active, plan.chunk)
            cohorts: OrderedDict = OrderedDict()
            for job in active:
                j0 = job.cursor
                n = min(ce - j0 % ce, job.n_chunks - j0)
                # runtime-degraded jobs peel into their own cohorts so
                # fused siblings never inherit their rung
                cohorts.setdefault((j0, n, job.backend, job.max_window),
                                   []).append(job)
            for (j0, n, _, _), cjobs in cohorts.items():
                # stream rows: first-seen dedupe by seed — jobs sharing a
                # seed consume ONE sample row (the shared-stream win);
                # pad to the group's stream width for shape stability
                row_of: dict = {}
                keys: list = []
                for job in cjobs:
                    if job.seed not in row_of:
                        row_of[job.seed] = len(keys)
                        keys.append(job.base_key)
                pad = group.n_streams - len(keys)
                base_keys = jnp.stack(keys + [keys[0]] * pad)
                drawn = plan.chunk * n * len(keys)
                profiling = obs.profile_armed()
                if profiling:
                    obs.profile_window_start()
                with obs.span("engine.dispatch", stage="dispatch",
                              trace=cjobs[0].trace,
                              backend=cjobs[0].backend, j0=int(j0),
                              n=int(n), samples=drawn, jobs=len(cjobs),
                              streams=len(keys), rung=cjobs[0].max_window,
                              plan_key=str(group.key.signature)) as sp:
                    sums, n_disp = _run_cohort_window(plan, group, get_fn,
                                                      cjobs, base_keys,
                                                      j0, n)
                    sp.set(dispatches=n_disp, backend=cjobs[0].backend)
                if profiling:
                    obs.profile_window_end()
                dt = sp.elapsed_s
                plan.dispatches += n_disp
                STATS.dispatches += n_disp
                STATS.job_windows += len(cjobs)
                if len(cjobs) > 1:
                    STATS.fused_dispatches += 1
                STATS.tree_cohorts += 1
                STATS.cohort_motif_lanes += len({j.lane for j in cjobs})
                STATS.samples_shared += (plan.chunk * n
                                         * (len(cjobs) - len(keys)))
                STATS.samples_drawn += drawn
                for job in cjobs:
                    wsums = {kk: int(sums[kk][row_of[job.seed], job.lane])
                             for kk in _ACC_KEYS}
                    for kk in _ACC_KEYS:
                        job.acc[kk] += wsums[kk]
                    job.cursor = j0 + n
                    job.sampling_s += dt
                    if job.witnesses:
                        with obs.span("engine.witness", trace=job.trace,
                                      backend=job.backend, j0=int(j0),
                                      n=int(n)):
                            _run_witness_window(plan, group, job, j0, n)
                    if job.checkpoint_path:
                        _write_checkpoint(job, plan.chunk)
                    if on_window is not None:
                        on_window(job, wsums, j0, n)
            active = [j for j in active if j.cursor < j.n_chunks]

    results = []
    for job in sorted(plan.jobs, key=lambda j: j.index):
        W = int(job.wts.W_total)
        # a deadline-degraded job answers for the samples it drew; its
        # partial is bit-identical to a clean run with budget k_done
        # (same fold_in keys, exact int64 sums)
        k_done = job.cursor * plan.chunk if job.degraded else job.k_eff
        # per-motif unbiasing: the job's OWN W and cnt2 over the (possibly
        # cohort-shared) sample stream — see estimator.unbias_estimate
        est = unbias_estimate(W, job.acc["cnt2"], k_done)
        results.append(EstimateResult(
            estimate=est,
            W=W, k=k_done, valid=job.acc["valid"],
            fail_vmap=job.acc["fail_vmap"], fail_delta=job.acc["fail_delta"],
            fail_order=job.acc["fail_order"], overflow=job.acc["overflow"],
            cnt2_sum=job.acc["cnt2"], motif=job.motif.name,
            tree_edges=job.tree.edge_ids, delta=int(job.delta),
            preprocess_s=job.preprocess_s, sampling_s=job.sampling_s,
            tree_select_s=job.tree_select_s, sampler_backend=job.backend,
            fallback_reason=job.fallback_reason,
            mesh_shape=plan.mesh_shape, fused_jobs=job.group_size,
            degraded=job.degraded, degrade_reason=job.degrade_reason,
            witnesses=(witness_entries(job.wit, job.witnesses)
                       if job.witnesses else None)))
    return results
