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
never run this.
"""
from __future__ import annotations

import argparse
import json
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


def answer_all(cell, seed: int, n_requests: int, g: R.Graph) -> list:
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
    answers = []
    for req in todo:
        ref, tree = chosen[req["motif"]]
        k = int(req["k"])
        d = R.sample(ref.wn, tree, ref.w[tree.shape], k,
                     np.random.default_rng(req["seed"]), lmax=lmax,
                     windows_corrected=False)
        answers.append(dict(motif=req["motif"], delta=req["delta"], k=k,
                            W=d.W, estimate=float(d.x.mean()),
                            failed=False))
    return answers


def control(cell, seed: int, n_requests: int) -> dict:
    g = R.Graph(*synth.generate(cell.config["graph"], seed))
    answers = answer_all(cell, seed, n_requests, g)
    return checker.check(answers, cell.config, g, cell.limits, seed)


def main(argv=None) -> int:
    from bench.run import Cell
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    cell = Cell.load(args.workload, trace=False)
    for seed in args.seeds:
        v = control(cell, seed, args.requests)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": v["correct"], "numbers": v["numbers"],
                          "info": v["info"]}, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
