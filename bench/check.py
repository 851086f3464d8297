"""Decide ``correct``: the served answers against the plain reference.

Every answer of the window is compared.  Per standing (motif, delta),
the reference (``bench/reference.py``) finds the rooted spanning tree
whose ``W`` equals the served one and draws its own samples on that
tree.  The numbers compared, each with the cell's limit from
``bench/limits/<cell>.json``:

* ``failed``: requests of the window never answered, or answered with
  an error or a degraded partial (limit 0);
* ``w_gap``: the largest relative distance of a served ``W`` from the
  nearest reference ``W`` (an exact comparison: limit 0);
* ``z_max``: the largest ``|estimate - C| / se`` over the answers, where
  ``C`` is the reference estimate and ``se`` combines the served and the
  reference sampling error at the served and reference budgets;
* ``z_pool``: the same for the budget-weighted mean of each motif's
  answers, the largest over the motifs.

Where the answers state an error (``target_rse``, screen traffic), two
more, each answer held to the target its request stated (as the mix
states it, not as it was sent):

* ``rse_miss``: answers whose reported ``rse`` is missing or above their
  target, those that reached ``k_max`` first among them (limit 0);
* ``rse_under``: how far the reported errors fall short of the true
  ones: one over the mean, across the answers that report an ``rse``,
  of ``rse / (sqrt(var / k) / C)``, the reported relative error over the
  reference's at the served budget.  Each term is about ``|Z|`` for
  batch means over two windows, light-tailed however few agree, so the
  mean over a window's answers is steady where one (motif, target)'s
  five answers are not; an answer that reports a quarter of its error
  moves it fourfold.  Answers whose reference error is 0 (an exact
  count) are left out.

``info`` also carries, per (motif, target) and the largest,
``rse_ratio``: the mean over the answers of the reference's relative
standard error at the served budget over the target.  It is not
compared: the server's stopping rule, batch means over as few as two
windows, stops sound answers at errors above their target, so that it
does not separate a sound program from one that asks for targets four
times looser (``rse_miss`` catches that one: it reports errors above
the stated targets; ``PERF.md`` sections 4 and 7).

The count is the one the configuration states: completion lists
longer than its ``lmax`` score 0 (the server's DeriveCnt cap, ROADMAP
R3).  A program that lifts the cap changes the configuration with it.
"""
from __future__ import annotations

import math

import numpy as np

from . import reference as R
from . import workmodel


def motif_edges(config: dict, motif: str) -> tuple:
    return tuple(tuple(e) for e in config["motifs"][motif])


#: z reported in place of an infinite one (no spread, answers differ)
Z_CAP = 1e12


def _z(est, C, var, k, k_ref) -> float:
    se = math.sqrt(var / k + var / k_ref)
    if se == 0:
        return 0.0 if est == C else Z_CAP
    return min(abs(est - C) / se, Z_CAP)


def _rel_se(C, var, k) -> float:
    """The reference's relative standard error of one answer of budget
    ``k``: ``sqrt(var / k) / C`` (0 for an exact zero count)."""
    if C == 0:
        return 0.0 if var == 0 else Z_CAP
    return min(math.sqrt(var / k) / abs(C), Z_CAP)


def check(answers: list, config: dict, g: R.Graph, limits: dict,
          seed: int) -> dict:
    """``answers``: dicts with ``motif``, ``delta``, ``k``, ``W``,
    ``estimate`` and ``failed`` (bool).  Returns ``{"correct": bool,
    "numbers": {name: [value, limit]}, "info": {...}}``."""
    lmax = int(config["server"]["lmax"])
    k_ref = int(limits["reference_samples"])
    failed = sum(1 for a in answers if a["failed"])
    good = [a for a in answers if not a["failed"]]
    by_motif: dict = {}
    for a in good:
        by_motif.setdefault((a["motif"], int(a["delta"])), []).append(a)
    w_gap, z_max, z_pool, info = 0.0, 0.0, 0.0, {}
    stated = [a for a in good if a.get("target_rse") is not None]
    rse_miss = sum(1 for a in stated
                   if a["rse"] is None or a["rse"] > a["target_rse"])
    shares, rse_ratio = [], 0.0
    bytes_per_sample = {}
    for n_key, ((motif, delta), ans) in enumerate(sorted(by_motif.items())):
        ref = R.reference_for(g, motif_edges(config, motif), delta)
        rng = np.random.default_rng(
            np.random.SeedSequence([int(seed) & (2**64 - 1), 7, n_key]))
        trees = []
        for a in ans:
            match = ref.match(int(a["W"]))
            if not match:
                w_gap = max(w_gap, ref.nearest_gap(int(a["W"])))
            elif not trees:
                trees = match
        if not trees:          # no W matched (every tree is computed
            trees = [ref.trees[0]]  # by now): go on with Alg. 7's first
        draws = [R.sample(ref.wn, t, ref.w[t.shape], k_ref, rng, lmax=lmax)
                 for t in trees]
        bytes_per_sample[motif] = workmodel.bytes_per_sample(
            draws[0], g, trees[0])
        C = float(draws[0].x.mean())
        var = max(float(d.x.var()) for d in draws)
        ks = np.array([int(a["k"]) for a in ans], float)
        ests = np.array([float(a["estimate"]) for a in ans])
        z_max = max(z_max, max(_z(e, C, var, k, k_ref)
                               for e, k in zip(ests, ks)))
        pooled = float((ks * ests).sum() / ks.sum())
        z_pool = max(z_pool, _z(pooled, C, var, ks.sum(), k_ref))
        by_target: dict = {}
        for a in ans:
            if a.get("target_rse") is None or a["rse"] is None:
                continue
            ref_se = _rel_se(C, var, int(a["k"]))
            by_target.setdefault(float(a["target_rse"]), []).append(ref_se)
            if ref_se > 0:
                shares.append(float(a["rse"]) / ref_se)
        for target, ref_se in sorted(by_target.items()):
            ratio = float(np.mean(ref_se)) / target
            rse_ratio = max(rse_ratio, ratio)
            info[f"{motif}.{target}.rse_ratio"] = ratio
            info[f"{motif}.{target}.answers"] = len(ref_se)
        info[f"{motif}.C"] = C
        info[f"{motif}.W"] = ref.W[trees[0].shape]
        info[f"{motif}.overflow"] = draws[0].overflow
        info[f"{motif}.answers"] = len(ans)
    numbers = {
        "failed": [failed, 0],
        "w_gap": [w_gap, 0.0],
        "z_max": [z_max, float(limits["z_max"])],
        "z_pool": [z_pool, float(limits["z_pool"])],
    }
    if stated:
        numbers["rse_miss"] = [rse_miss, 0]
        mean = float(np.mean(shares)) if shares else 1.0
        numbers["rse_under"] = [1.0 / mean if mean > 0 else Z_CAP,
                                float(limits["rse_under"])]
        info["rse_ratio"] = rse_ratio
    info["bytes_per_sample"] = bytes_per_sample
    correct = bool(answers) and all(v <= lim for v, lim in numbers.values())
    return {"correct": correct, "numbers": numbers, "info": info}
