"""End-to-end TIMEST estimation (paper Alg. 6/7).

``estimate()`` is a compatibility shim over the session API
(repro.api): it wraps the graph in a one-shot ``Session`` and submits a
single ``Request``.  The session plans (tree selection Alg. 7 + weight
preprocessing Alg. 1/2, via ``core.batch.BatchPlanner``) and hands the
job to the execution engine (core/engine.py), which samples in
``checkpoint_every``-aligned windows of chunks.  The chunk loop is
restartable: chunk ``j`` always uses ``fold_in(base_key, j)``, so a
checkpoint of ``(chunks_done, accumulators)`` resumes bit-identically
after a failure — on any mesh shape (see the engine's determinism
contract).  This module keeps ``choose_tree`` (Alg. 7, used directly by
benchmarks/tree sweeps) and the ``EstimateResult`` container.
"""
from __future__ import annotations

from dataclasses import dataclass

from ..util import ensure_x64

ensure_x64()

from .graph import TemporalGraph  # noqa: E402
from .motif import TemporalMotif  # noqa: E402
from .spanning_tree import SpanningTree, candidate_trees  # noqa: E402
from .weights import Weights, preprocess  # noqa: E402

_ACC_KEYS = ("cnt2", "valid", "fail_vmap", "fail_delta", "fail_order",
             "overflow")


def unbias_estimate(W: int, cnt2_sum: int, k: int) -> float:
    """Alg. 6 unbiasing: ``C^ = W * sum(cnt2) / (2k)``.

    In a tree-cohort (engine shared-sample multi-motif path) this is the
    per-motif correction: every lane applies its OWN ``W`` and ``cnt2``
    accumulator over the SHARED instance stream.  The stream's Alg. 3
    distribution depends only on the tree signature (which all lanes
    share), so ``E[cnt2]`` under it is each motif's own count and the
    per-lane estimate stays unbiased — and, because the accumulator is an
    exact int64 sum keyed by (seed, chunk) alone, bit-identical to the
    motif's solo run at the same budget.
    """
    return W * cnt2_sum / (2.0 * k) if k else 0.0


@dataclass
class EstimateResult:
    estimate: float
    W: int
    k: int                      # samples drawn
    valid: int
    fail_vmap: int
    fail_delta: int
    fail_order: int
    overflow: int
    cnt2_sum: int
    motif: str
    tree_edges: tuple
    delta: int
    preprocess_s: float = 0.0
    sampling_s: float = 0.0
    tree_select_s: float = 0.0
    sampler_backend: str = "xla"   # the backend that actually sampled
    fallback_reason: str = ""      # why the requested backend was vetoed
    mesh_shape: tuple | None = None   # data-sharding mesh, None = 1 device
    fused_jobs: int = 1            # jobs sharing this job's fused group
    # empirical batch-means relative standard error, filled by the
    # session layer (api/session.py); None when no session measured it
    rse: float | None = None
    # deadline partials: the job stopped at its last completed checkpoint
    # window, ``k`` reports the samples actually drawn (never an error)
    degraded: bool = False
    degrade_reason: str = ""
    # up to ``Request.witnesses`` accepted full-match edge tuples from the
    # deterministic reservoir (``engine.witness_entries`` format: dicts of
    # ``edges``/``cnt``/``prio``, edges in motif pi order); None when the
    # request did not ask for witnesses
    witnesses: tuple | None = None

    @property
    def valid_rate(self) -> float:
        return self.valid / max(self.k, 1)

    def summary(self) -> str:
        return (f"{self.motif}: C^={self.estimate:.6g}  W={self.W}  "
                f"k={self.k}  valid={100 * self.valid_rate:.1f}%  "
                f"(pre {self.preprocess_s:.2f}s + samp {self.sampling_s:.2f}s)")


def choose_tree(g: TemporalGraph, motif: TemporalMotif, delta: int,
                n_candidates: int = 3, roots_per_tree: int = 2,
                dev: dict | None = None, use_c2: bool = True,
                use_c3: bool = True) -> tuple[SpanningTree, Weights]:
    """Alg. 7: looseness-ranked candidates, exact W for top-k, min-W wins.

    The per-sample cost is identical across trees of the same motif (same
    |E(S)|, same number of non-tree lists), so Theorem 4.14 makes the
    estimated runtime monotone in W — the tree with the smallest total
    sampling weight is the fastest to converge.  Returns the winner together
    with its (already computed) Weights so preprocessing is never repeated.
    """
    if dev is None:
        dev = g.device_arrays()
    cands = candidate_trees(motif, n_candidates=n_candidates,
                            roots_per_tree=roots_per_tree)
    best: tuple[int, SpanningTree, Weights] | None = None
    for tree in cands:
        w = preprocess(g, tree, delta, dev=dev, use_c2=use_c2, use_c3=use_c3)
        Wt = int(w.W_total)
        if best is None or Wt < best[0]:
            best = (Wt, tree, w)
    assert best is not None
    return best[1], best[2]


def estimate(g: TemporalGraph, motif: TemporalMotif, delta: int, k: int,
             seed: int = 0, tree: SpanningTree | None = None,
             n_candidates: int = 3, chunk: int = 8192, Lmax: int = 16,
             use_c2: bool = True, use_c3: bool = True,
             checkpoint_path: str | None = None, checkpoint_every: int = 64,
             dev: dict | None = None,
             wts: Weights | None = None,
             sampler_backend: str | None = None,
             depsum_backend: str | None = None,
             mesh=None) -> EstimateResult:
    """Alg. 6: the full TIMEST estimate with ``k`` samples.

    ``wts`` (with ``tree``) injects precomputed weights — the batch
    engine's shared-preprocess path (core/batch.py).

    ``sampler_backend`` ("xla" | "pallas", default env
    ``REPRO_SAMPLER_BACKEND``) routes sampling through the fused
    kernels/tree_sampler Pallas kernel; ``depsum_backend`` likewise
    routes weight preprocessing; results are bit-identical.  The
    pallas path silently downgrades to xla when the job sits outside the
    kernel envelope (weights past f32-exact 2^24, time bounds past int32,
    or VMEM budget) — the backend actually used and the veto reason are
    recorded on the result.

    ``mesh`` (a ``jax.sharding.Mesh``, e.g. from
    ``launch.mesh.make_estimator_mesh``) shards each window's chunk range
    over the mesh's data axes; the estimate stays bit-identical to the
    unsharded run (engine determinism contract).

    This is a compatibility shim over the session API (repro.api): it
    builds a one-shot ``Session`` around the graph and submits a single
    ``Request`` — bit-identical to the pre-session implementation
    (pinned by tests/test_api.py goldens).  Callers issuing several
    related queries should hold a ``Session`` instead and let its
    preprocess cache and coalescing windows amortize the shared work.
    """
    from ..api import EstimateConfig, Request, Session
    cfg = EstimateConfig(chunk=chunk, Lmax=Lmax,
                         checkpoint_every=checkpoint_every,
                         n_candidates=n_candidates, use_c2=use_c2,
                         use_c3=use_c3, sampler_backend=sampler_backend,
                         depsum_backend=depsum_backend, seed=int(seed))
    session = Session(g, cfg, dev=dev, mesh=mesh)
    handle, = session.submit_many([Request(
        motif=motif, delta=int(delta), k=int(k), seed=int(seed),
        checkpoint_path=checkpoint_path, tree=tree, wts=wts)])
    return handle.result()
