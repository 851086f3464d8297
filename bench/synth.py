"""The benchmark's own graph generators: exact shapes from any seed.

Copies of the ``powerlaw`` and ``fintxn`` generators of the program's
``graphs/synth.py`` (Chung-Lu endpoints, bursty timestamps, temporal
repeats of a pair; planted laundering rings, scatter-gather and
bipartite layering), changed so that every seed yields exactly the
configuration's

* ``m`` edges, all distinct ``(src, dst, t)`` triples, no self-loops;
* ``n`` vertices, ids ``0 .. n-1``, each one an endpoint of some edge;
* ``pairs`` distinct ordered ``(src, dst)`` pairs;
* time span ``[0, time_span]``, both ends taken.

The program's graph arrays and window tables have exactly those sizes,
so every seed presents the same shapes and finds the same compiled
programs.  Only the wiring and the times move with the seed.

``generate(params, seed)`` returns ``(src, dst, t)`` as int64 arrays;
``graph_path`` writes them once per (configuration, seed) as ``.npz``,
the format the program's ``load_edge_list`` reads.
"""
from __future__ import annotations

import os

import numpy as np

#: per-edge gap of a repeat on the same pair: geometric(p), as the source
REPEAT_P = 0.002


def _rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) & (2**64 - 1), tag]))


def _weights(n: int, alpha: float) -> np.ndarray:
    """Chung-Lu endpoint weights: vertex i gets (i+1)^(-1/(alpha-1))."""
    w = np.arange(1, n + 1, dtype=np.float64) ** (-1.0 / (alpha - 1.0))
    return w / w.sum()


def _planted(p: dict, rng: np.random.Generator) -> tuple:
    """The fintxn structures: temporal 5-cycles, scatter-gather and 2x3
    bipartite layering, each on distinct random accounts, its edges in
    time order with gaps of 1..gap-1."""
    n, span = p["n"], p["time_span"]
    groups = []                    # (vertex tuples [G, k], edge (a, b) list, gap)
    R, rs = p.get("n_rings", 0), p.get("ring_size", 5)
    if R:
        groups.append((R, rs, [(i, (i + 1) % rs) for i in range(rs)], 50))
    S = p.get("n_smurf", 0)
    if S:
        groups.append((S, 5, [(0, 1), (0, 2), (0, 3), (1, 4), (2, 4),
                              (3, 4)], 40))
        groups.append((S // 2, 5, [(0, 2), (0, 3), (0, 4), (1, 2), (1, 3),
                                   (1, 4)], 40))
    src, dst, t = [], [], []
    for G, k, edges, gap in groups:  # G groups of k accounts
        if G == 0:
            continue
        verts = _distinct_rows(rng, G, k, n)
        e = len(edges)
        gaps = rng.integers(1, gap, size=(G, e))
        gaps[:, 0] = 0
        start = rng.integers(0, span - gap * e, size=G)
        tt = start[:, None] + np.cumsum(gaps, axis=1)
        a = np.array([x for x, _ in edges])
        b = np.array([y for _, y in edges])
        src.append(verts[:, a].ravel())
        dst.append(verts[:, b].ravel())
        t.append(tt.ravel())
    if not src:
        z = np.zeros(0, np.int64)
        return z, z, z
    return (np.concatenate(src).astype(np.int64),
            np.concatenate(dst).astype(np.int64),
            np.concatenate(t).astype(np.int64))


def _distinct_rows(rng, G: int, k: int, n: int) -> np.ndarray:
    """G rows of k distinct ids in [0, n): draw, then redraw rows that
    repeat an id (rare for k << n)."""
    out = rng.integers(0, n, size=(G, k))
    while True:
        s = np.sort(out, axis=1)
        bad = np.any(s[:, 1:] == s[:, :-1], axis=1)
        if not bad.any():
            return out
        out[bad] = rng.integers(0, n, size=(int(bad.sum()), k))


def _times(p: dict, rng: np.random.Generator, size: int) -> np.ndarray:
    """The source's bursty times: a share ``burstiness`` clustered around
    hot spots (sd 1% of the span), the rest uniform."""
    span = p["time_span"]
    n_hot = max(4, span // 5000)
    hot = rng.integers(0, span, size=n_hot)
    burst = rng.random(size) < p["burstiness"]
    t = np.where(burst,
                 hot[rng.integers(0, n_hot, size=size)]
                 + rng.normal(0, span * 0.01, size=size).astype(np.int64),
                 rng.integers(0, span, size=size))
    return np.clip(t, 0, span)


def _pairs(p: dict, rng, wts, fixed: np.ndarray, n_pairs: int) -> np.ndarray:
    """Exactly ``n_pairs`` distinct directed pair keys ``u*n+v`` covering
    every vertex: the ``fixed`` (planted) pairs, then Chung-Lu pairs in
    draw order, then one pair per still-uncovered vertex to a covered
    Chung-Lu partner.  The Chung-Lu prefix length L is the one for which
    fixed + L + uncovered(L) == n_pairs."""
    n = p["n"]
    fixed = np.unique(fixed)
    seen = np.zeros(n, bool)
    seen[fixed // n] = True
    seen[fixed % n] = True
    size = 2 * n_pairs
    while True:
        u = rng.choice(n, size=size, p=wts)
        v = rng.choice(n, size=size, p=wts)
        key = u * n + v
        ok = u != v
        key = key[ok]
        _, first = np.unique(key, return_index=True)
        first.sort()
        key = key[first]
        key = key[~np.isin(key, fixed)]
        # first Chung-Lu prefix position at which each vertex appears
        first_at = np.full(n, np.iinfo(np.int64).max)
        idx = np.arange(len(key), dtype=np.int64)
        np.minimum.at(first_at, key // n, idx + 1)
        np.minimum.at(first_at, key % n, idx + 1)
        first_at[seen] = 0
        order = np.sort(first_at)
        L = np.arange(len(key) + 1)
        uncovered = n - np.searchsorted(order, L, side="right")
        total = len(fixed) + L + uncovered
        hit = np.nonzero(total == n_pairs)[0]
        if hit.size:
            break
        if total.min() > n_pairs:     # more draws only add pairs
            raise ValueError(f"{n_pairs} pairs cannot cover {n} vertices")
        size *= 2
    L = int(hit[0])
    chosen = key[:L]
    covered = seen.copy()
    covered[chosen // n] = True
    covered[chosen % n] = True
    lone = np.nonzero(~covered)[0]
    if lone.size:
        cw = np.where(covered, wts, 0.0)
        partner = rng.choice(n, size=lone.size, p=cw / cw.sum())
        out = rng.random(lone.size) < 0.5
        extra = np.where(out, lone * n + partner, partner * n + lone)
        chosen = np.concatenate([chosen, extra])
    return np.concatenate([fixed, chosen])


def generate(p: dict, seed: int) -> tuple:
    """``(src, dst, t)`` with exactly the sizes of ``p`` (see the module
    docstring).  ``p`` holds ``n``, ``m``, ``pairs``, ``time_span``,
    ``alpha``, ``burstiness``, ``multiplicity`` and, for ``fintxn``,
    ``n_rings``/``ring_size``/``n_smurf``."""
    n, m, span = int(p["n"]), int(p["m"]), int(p["time_span"])
    rng = _rng(seed, 0)
    wts = _weights(n, p["alpha"])
    ps, pd, pt = _planted(p, _rng(seed, 1))
    fixed = ps * n + pd
    keys = _pairs(p, rng, wts, fixed, int(p["pairs"]))
    planted_pairs = np.unique(fixed)
    bg = keys[len(planted_pairs):]
    # base edges: one per background pair, the rest Chung-Lu over pairs
    m_rep = int(round(p["multiplicity"] * m))
    extra = m - len(ps) - m_rep - len(bg)
    if extra < 0:
        raise ValueError(f"{len(bg)} pairs need more than {m} edges")
    pw = wts[bg // n] * wts[bg % n]
    base = np.concatenate([bg, bg[rng.choice(len(bg), size=extra,
                                             p=pw / pw.sum())]])
    bt = _times(p, rng, len(base))
    # temporal repeats of a base edge's pair at a nearby time
    pick = rng.integers(0, len(base), size=m_rep)
    rt = np.clip(bt[pick] + rng.geometric(REPEAT_P, size=m_rep), 0, span)
    key = np.concatenate([fixed, base, base[pick]])
    t = np.concatenate([pt, bt, rt])
    t = _unique_times(key, t, span)
    src, dst = key // n, key % n
    _check(p, src, dst, t)
    return src, dst, t


def _unique_times(key, t, span) -> np.ndarray:
    """Make every (pair, t) distinct with the least forward push: within
    each pair, in time order, ``t_j = max(t_j, t_(j-1) + 1)``, capped so
    the pair's last edge stays at or before ``span``.  Then put the
    earliest edge at 0 and the latest at ``span``."""
    o = np.lexsort((t, key))
    ks, ts = key[o], t[o]
    head = np.r_[True, ks[1:] != ks[:-1]]
    seg = np.cumsum(head) - 1
    first = np.nonzero(head)[0]
    j = np.arange(len(ts)) - first[seg]
    left = np.bincount(seg)[seg] - 1 - j        # edges after j in its pair
    big = np.int64(span + len(ts) + 2)
    t1 = np.maximum.accumulate(ts - j + seg * big) - seg * big + j
    out = np.empty_like(t)
    out[o] = np.minimum(t1, span - left)
    out[np.argmin(out)] = 0
    out[np.argmax(out)] = span
    return out


def _check(p: dict, src, dst, t) -> None:
    n = int(p["n"])
    key = src * n + dst
    got = dict(m=len(t), n=len(np.unique(np.concatenate([src, dst]))),
               pairs=len(np.unique(key)), time_span=int(t.max()),
               t0=int(t.min()),
               triples=len(np.unique(key * (int(p["time_span"]) + 1) + t)),
               loops=int((src == dst).sum()))
    want = dict(m=int(p["m"]), n=n, pairs=int(p["pairs"]),
                time_span=int(p["time_span"]), t0=0, triples=int(p["m"]),
                loops=0)
    if got != want:
        raise RuntimeError(f"generated graph {got} differs from {want}")


def graph_path(data_dir: str, config: str, p: dict, seed: int) -> str:
    """The ``.npz`` of (configuration, seed), generated on first use."""
    path = os.path.join(data_dir, f"{config}.{int(seed)}.npz")
    if not os.path.exists(path):
        os.makedirs(data_dir, exist_ok=True)
        src, dst, t = generate(p, seed)
        tmp = path + ".part.npz"
        np.savez(tmp, src=src.astype(np.int32), dst=dst.astype(np.int32),
                 t=t.astype(np.int64))
        os.replace(tmp, path)
    return path


def load(path: str) -> tuple:
    z = np.load(path)
    return (z["src"].astype(np.int64), z["dst"].astype(np.int64),
            z["t"].astype(np.int64))
