"""The control: the reference in the program's place, one guarantee broken.

    python3 -m bench.control --workload aml.batch --seeds 11 12 13 --requests 8

For each seed it builds the cell's graph and the first ``--requests``
requests of the cell's traffic, and answers each as the server would --
tree chosen by paper Alg. 7 (least W among the leading candidates), the
request's budget drawn -- with the reference estimator
that leaves out the division by the number of windows holding a match
(Lemma 4.12): every match that two windows hold counts twice.  It then
runs the cell's check on those answers and prints its numbers.  The
check must come out not correct on every seed; the benchmark's own runs
never run this.  ``--sound`` keeps the division: the reference in the
program's place, whose readings are sound ones.

A request that states an error is answered as the server's adaptive
budget answers it: windows of ``chunk * checkpoint_every`` samples, the
batch-means relative standard error over the windows drawn so far, and
rounds that grow the budget by ``RSE_GROWTH`` (new samples only) until
that error is at most the target or ``k_max`` is reached.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check as checker  # noqa: E402
from bench import reference as R  # noqa: E402
from bench import synth, traffic  # noqa: E402

#: Alg. 7's candidates: 3 tightest trees x 2 roots
N_CANDIDATES = 6
#: the server's budget growth per round (its default, which no mix sets)
RSE_GROWTH = 2.0


def batch_rse(sums: list, sizes: list) -> float:
    """The batch-means relative standard error over windows with score
    sums ``sums`` of ``sizes`` samples (inf below two windows)."""
    total = sum(sums)
    if len(sums) < 2 or total <= 0:
        return math.inf
    mu = total / sum(sizes)
    var = sum((S - k * mu) ** 2 for S, k in zip(sums, sizes)) / (len(sums)
                                                                 - 1)
    return math.sqrt(len(sums) * var) / total


def adaptive(draw, req: dict, chunk: int, per_window: int) -> tuple:
    """``(k, scores, rse)`` of a request as the adaptive budget serves
    it; ``draw(k)`` gives ``k`` fresh per-sample scores."""
    n_chunks = -(-int(req["k"]) // chunk)
    cap = max(1, -(-int(req["k_max"]) // chunk))
    x, sums, sizes = np.zeros(0), [], []
    while True:
        new = draw(n_chunks * chunk - len(x))
        for lo in range(0, len(new), per_window):
            part = new[lo:lo + per_window]
            sums.append(float(part.sum()))
            sizes.append(len(part))
        x = np.concatenate([x, new])
        rse = batch_rse(sums, sizes)
        if rse <= req["target_rse"] or n_chunks >= cap:
            return len(x), x, rse
        k_total = max(int(n_chunks * chunk * RSE_GROWTH), len(x) + chunk)
        n_chunks = -(-min(int(req["k_max"]), k_total) // chunk)


def answer_all(cell, seed: int, n_requests: int, g: R.Graph,
               sound: bool = False) -> list:
    mix, cfg = cell.mix, cell.config
    lmax = int(cfg["server"]["lmax"])
    reqs = traffic.Requests(mix, cfg["standing"], seed)
    clients = int(mix["clients"])
    todo = [reqs.request(c, i) for i in range(n_requests) for c in
            range(clients)][:n_requests]
    chosen = {}
    for motif, delta in cfg["standing"]:
        ref = R.reference_for(g, checker.motif_edges(cfg, motif), int(delta))
        for tree in ref.trees[:N_CANDIDATES]:
            if tree.shape not in ref.W:
                w = R.weights(ref.wn, tree)
                ref.W[tree.shape], ref.w[tree.shape] = int(w[tree.root].sum()), w
        best = min(ref.trees[:N_CANDIDATES], key=lambda t: ref.W[t.shape])
        chosen[motif] = (ref, best)
    chunk = int(mix["server"]["chunk"])
    per_window = chunk * int(mix["server"]["checkpoint_every"])
    answers = []
    for req in todo:
        ref, tree = chosen[req["motif"]]
        rng = np.random.default_rng(req["seed"])

        def draw(k):
            return R.sample(ref.wn, tree, ref.w[tree.shape], k, rng,
                            lmax=lmax, windows_corrected=sound).x
        a = dict(motif=req["motif"], delta=req["delta"],
                 W=ref.W[tree.shape], failed=False)
        if "target_rse" in req:
            k, x, rse = adaptive(draw, req, chunk, per_window)
            a.update(target_rse=req["target_rse"],
                     rse=None if math.isinf(rse) else rse)
        else:
            k = int(req["k"])
            x = draw(k)
        answers.append(dict(a, k=k, estimate=float(x.mean())))
    return answers


def control(cell, seed: int, n_requests: int, sound: bool = False) -> dict:
    g = R.Graph(*synth.generate(cell.config["graph"], seed))
    answers = answer_all(cell, seed, n_requests, g, sound)
    v = checker.check(answers, cell.config, g, cell.limits, seed)
    v["info"]["k_served"] = [a["k"] for a in answers]
    return v


def main(argv=None) -> int:
    from bench.run import Cell
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, required=True)
    ap.add_argument("--sound", action="store_true",
                    help="keep the division: a sound reading, no control")
    args = ap.parse_args(argv)
    cell = Cell.load(args.workload, trace=False)
    for seed in args.seeds:
        v = control(cell, seed, args.requests, args.sound)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "sound": args.sound,
                          "correct": v["correct"], "numbers": v["numbers"],
                          "info": v["info"]}, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
