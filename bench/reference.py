"""Plain reference of the TIMEST estimator, in numpy, from the paper.

It imports nothing of the program and reads only the edge list.  For a
motif (edges in pi order) and a window ``delta`` it computes, for a
rooted spanning tree of the motif:

* ``W``: the number of (window, delta-partial match) pairs, where window
  ``i`` holds the edges with ``t`` in ``[i*delta, (i+2)*delta)`` and a
  partial match maps each tree edge to a graph edge so that every child
  meets its parent at the shared vertex with the motif's direction, lies
  within ``delta`` on the motif's side of it (closed bounds), and does
  not end at the parent's other vertex (Alg. 1/2, Claims 4.8-4.10);
* samples drawn uniformly from those pairs with numpy's own generator
  (Alg. 3), each validated (injective vertex map, all tree edges within
  ``delta``, strictly in pi order; Alg. 4) and scored by its number of
  completions through the non-tree edges over its number of windows
  (Alg. 5, Lemma 4.12), a completion list longer than ``lmax`` scoring
  0 as the server computes it (ROADMAP R3).

``W`` times a sample's score has mean equal to the motif count, so the
reference estimate and its per-sample variance are those of the served
estimator on the same rooted tree, whatever code computes it.  All
counts are exact int64.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import numpy as np

OUT, IN = 1, -1


@dataclass(frozen=True)
class Dep:
    child: int        # tree-local index of the child edge
    meet_end: int     # 0: child meets the parent's src vertex, 1: its dst
    alpha: int        # OUT: the child leaves the meeting vertex, IN: enters
    before: bool      # the child's pi rank is below the parent's


@dataclass(frozen=True)
class Tree:
    motif: tuple      # motif edges (x, y) in pi order
    edges: tuple      # motif edge ids of the tree
    root: int         # tree-local index of the root edge
    deps: tuple       # per tree-local edge: its Dep tuple
    down: tuple       # tree-local order, parents before children

    @property
    def shape(self) -> tuple:
        """What W and the sample distribution depend on: the rooted
        structure, not which motif edges it uses."""
        return (self.root, self.deps, self.down)


def rooted_tree(motif: tuple, subset: tuple, root_edge: int) -> Tree:
    """Root the spanning tree ``subset`` at motif edge ``root_edge``: the
    root introduces both its vertices; every other tree edge hangs off
    the edge that introduced the vertex it shares with it."""
    ends = [motif[e] for e in subset]
    k = len(subset)
    root = subset.index(root_edge)
    intro = {ends[root][0]: root, ends[root][1]: root}
    deps = [[] for _ in range(k)]
    down, frontier, seen = [root], [root], {root}
    while frontier:
        nxt = []
        for s in frontier:
            for c in range(k):
                if c in seen:
                    continue
                shared = set(ends[s]) & set(ends[c])
                if not shared:
                    continue
                a = shared.pop()
                if intro.get(a) != s:
                    continue
                seen.add(c)
                deps[s].append(Dep(child=c,
                                   meet_end=0 if a == ends[s][0] else 1,
                                   alpha=OUT if ends[c][0] == a else IN,
                                   before=subset[c] < subset[s]))
                far = ends[c][1] if ends[c][0] == a else ends[c][0]
                intro[far] = c
                nxt.append(c)
                down.append(c)
        frontier = nxt
    if len(seen) != k:
        raise ValueError(f"{subset} is not a spanning tree")
    return Tree(motif=tuple(motif), edges=tuple(subset), root=root,
                deps=tuple(tuple(d) for d in deps), down=tuple(down))


def _spanning_subsets(motif: tuple) -> list:
    nv = 1 + max(max(e) for e in motif)
    out = []
    for sub in itertools.combinations(range(len(motif)), nv - 1):
        par = list(range(nv))

        def find(x):
            while par[x] != x:
                x = par[x]
            return x
        ok = True
        for e in sub:
            a, b = find(motif[e][0]), find(motif[e][1])
            if a == b:
                ok = False
                break
            par[a] = b
        if ok:
            out.append(sub)
    return out


def _looseness(motif: tuple, subset: tuple) -> int:
    """Paper Alg. 8: sum over vertices of |rank gap - 1| of tree edges."""
    nv = 1 + max(max(e) for e in motif)
    total = 0
    for u in range(nv):
        inc = [e for e in subset if u in motif[e]]
        for a, b in itertools.combinations(inc, 2):
            total += abs(abs(a - b) - 1)
    return total


def _height(t: Tree, s: int) -> int:
    return 1 + max((_height(t, d.child) for d in t.deps[s]), default=-1)


def rooted_trees(motif: tuple) -> list:
    """Every rooted spanning tree of the motif, those that paper Alg. 7
    ranks first (tightest ordering; median-rank root, then the root of
    least height) leading."""
    subs = sorted(_spanning_subsets(motif),
                  key=lambda s: (_looseness(motif, s), s))
    lead, rest = [], []
    for sub in subs:
        roots = [sorted(sub)[len(sub) // 2]]
        heights = [(_height(rooted_tree(motif, sub, r),
                            sub.index(r)), r) for r in sub]
        best = min(heights, key=lambda hr: hr[0])[1]
        if best not in roots:
            roots.append(best)
        lead += [rooted_tree(motif, sub, r) for r in roots]
        rest += [rooted_tree(motif, sub, r) for r in sub if r not in roots]
    return lead + rest


class Graph:
    """The edge list as the reference indexes it: vertices relabelled in
    id order, times from 0, and per window the (edge, window) entries in
    three key orders."""

    def __init__(self, src, dst, t):
        src, dst, t = (np.asarray(a, np.int64) for a in (src, dst, t))
        verts, inv = np.unique(np.concatenate([src, dst]),
                               return_inverse=True)
        self.n = len(verts)
        self.m = len(t)
        self.src, self.dst = inv[:self.m], inv[self.m:]
        self.t = t - t.min()
        self.span = int(self.t.max())
        pk = self.src * self.n + self.dst
        self.pair_keys, self.pid = np.unique(pk, return_inverse=True)
        # pair list order: (pair, t), for completion counts
        self.pair_order = np.lexsort((self.t, self.pid))
        self.pair_sorted_key = (self.pid[self.pair_order] * (self.span + 1)
                                + self.t[self.pair_order])
        self._windows: dict = {}

    def pair_of(self, u, v) -> np.ndarray:
        """Pair id of each (u, v), -1 where the graph has no such edge."""
        k = u * self.n + v
        i = np.clip(np.searchsorted(self.pair_keys, k), 0,
                    len(self.pair_keys) - 1)
        return np.where(self.pair_keys[i] == k, i, -1)

    def count_between(self, pid, lo, hi) -> np.ndarray:
        """Edges of pair ``pid`` with ``lo <= t <= hi`` (0 for pid -1)."""
        T = self.span + 1
        lo = np.clip(lo, 0, T)
        hi = np.clip(hi, -1, self.span)
        base = np.maximum(pid, 0) * T
        a = np.searchsorted(self.pair_sorted_key, base + lo, side="left")
        b = np.searchsorted(self.pair_sorted_key, base + hi, side="right")
        return np.where((pid >= 0) & (hi >= lo), b - a, 0)

    def times_between(self, pid: int, lo: int, hi: int) -> np.ndarray:
        T = self.span + 1
        a = np.searchsorted(self.pair_sorted_key, pid * T + max(lo, 0))
        b = np.searchsorted(self.pair_sorted_key,
                            pid * T + min(hi, self.span), side="right")
        return self.t[self.pair_order[a:b]] if pid >= 0 and hi >= lo \
            else self.t[:0]

    def windows(self, delta: int) -> "Windows":
        if delta not in self._windows:
            self._windows[delta] = Windows(self, int(delta))
        return self._windows[delta]


class Windows:
    """(edge, window) entries for one ``delta`` and their key orders."""

    def __init__(self, g: Graph, delta: int):
        self.g, self.delta = g, delta
        wd = max(delta, 1)
        self.wd = wd
        self.q = max(1, -(-(g.span + 1) // wd) - 1)
        fl = g.t // wd
        own = fl <= self.q - 1
        prev = fl >= 1
        e = np.arange(g.m)
        self.edge = np.concatenate([e[own], e[prev]])
        self.win = np.concatenate([fl[own], fl[prev] - 1])
        T = g.span + 1
        if self.q * max(g.n, len(g.pair_keys)) * T >= 1 << 62:
            raise OverflowError("window keys do not fit int64")
        te = g.t[self.edge]
        self.key = {}
        for name, v in (("out", g.src[self.edge]), ("in", g.dst[self.edge]),
                        ("pair", g.pid[self.edge])):
            width = len(g.pair_keys) if name == "pair" else g.n
            k = (self.win * width + v) * T + te
            o = np.argsort(k, kind="stable")
            self.key[name] = (o, k[o], width)

    def lookup(self, name: str, win, v, lo, hi):
        """Positions [a, b) in order ``name`` of window-``win`` entries
        keyed ``v`` with ``lo <= t <= hi``."""
        _, ks, width = self.key[name]
        T = self.g.span + 1
        base = (win * width + v) * T
        a = np.searchsorted(ks, base + np.clip(lo, 0, T), side="left")
        b = np.searchsorted(ks, base + np.clip(hi, -1, self.g.span),
                            side="right")
        return a, np.maximum(a, b)


def _bounds(dep: Dep, te, win, delta: int, wd: int):
    if dep.before:
        return np.maximum(te - delta, win * wd), te
    return te, np.minimum(te + delta, (win + 2) * wd - 1)


def _meet(dep: Dep, g: Graph, e):
    """(meeting vertex, the parent's other vertex) per parent edge."""
    if dep.meet_end == 0:
        return g.src[e], g.dst[e]
    return g.dst[e], g.src[e]


def _dep_parts(wn: Windows, dep: Dep, ent):
    """For parent entries ``ent``: the child's candidate range in the
    alpha order, and the excluded range (child ending at the parent's
    other vertex) in the pair order."""
    g = wn.g
    e, win = wn.edge[ent], wn.win[ent]
    v, b = _meet(dep, g, e)
    lo, hi = _bounds(dep, g.t[e], win, wn.delta, wn.wd)
    a1, b1 = wn.lookup("out" if dep.alpha == OUT else "in", win, v, lo, hi)
    pid = g.pair_of(v, b) if dep.alpha == OUT else g.pair_of(b, v)
    a2, b2 = wn.lookup("pair", win, np.maximum(pid, 0), lo, hi)
    b2 = np.where(pid >= 0, b2, a2)
    return (a1, b1), (a2, b2), b


def weights(wn: Windows, tree: Tree) -> list:
    """Per tree-local edge, the weight of every entry: its number of
    partial matches of the subtree below it, inside the entry's window."""
    w = [None] * len(tree.edges)
    ent = np.arange(len(wn.edge))
    for s in reversed(tree.down):
        ws = np.ones(len(ent), np.int64)
        for dep in tree.deps[s]:
            (a1, b1), (a2, b2), _ = _dep_parts(wn, dep, ent)
            name = "out" if dep.alpha == OUT else "in"
            c1 = _prefix(w[dep.child], wn.key[name][0])
            c2 = _prefix(w[dep.child], wn.key["pair"][0])
            ws = ws * ((c1[b1] - c1[a1]) - (c2[b2] - c2[a2]))
        w[s] = ws
    return w


def _prefix(w, order):
    return np.concatenate([np.zeros(1, np.int64), np.cumsum(w[order])])


@dataclass
class Draw:
    W: int
    x: np.ndarray        # per sample: W * completions / windows, lists
    #                      longer than lmax scoring 0 (the server's cap)
    overflow: float      # share of samples with a list past lmax
    spans: dict          # per-sample range lengths the work model reads


def sample(wn: Windows, tree: Tree, w: list, k: int, rng, *, lmax: int,
           windows_corrected: bool = True) -> Draw:
    """``k`` uniform (window, partial match) pairs, validated and scored.
    ``windows_corrected=False`` drops the division by the number of
    windows holding a match (the control's broken guarantee)."""
    g, root = wn.g, tree.root
    W = int(np.sum(w[root]))
    cw = np.cumsum(w[root])
    r = rng.integers(0, max(W, 1), size=k, dtype=np.int64)
    ent = [None] * len(tree.edges)
    ent[root] = np.minimum(np.searchsorted(cw, r, side="right"),
                           len(cw) - 1)
    win_size = np.bincount(wn.win, minlength=wn.q)
    spans = {"windows": wn.q, "root": win_size[wn.win[ent[root]]],
             "child": []}
    for s in tree.down:
        for dep in tree.deps[s]:
            name = "out" if dep.alpha == OUT else "in"
            order = wn.key[name][0]
            c1 = _prefix(w[dep.child], order)
            (a1, b1), _, b = _dep_parts(wn, dep, ent[s])
            spans["child"].append(b1 - a1)
            lam = c1[b1] - c1[a1]
            pick = np.zeros(k, np.int64)
            todo = np.arange(k)
            for _ in range(100000):
                rr = rng.integers(0, np.maximum(lam[todo], 1),
                                  dtype=np.int64)
                pos = np.searchsorted(c1, c1[a1[todo]] + rr,
                                      side="right") - 1
                pick[todo] = order[np.clip(pos, 0, len(order) - 1)]
                far = (g.dst if dep.alpha == OUT else g.src)[
                    wn.edge[pick[todo]]]
                todo = todo[(far == b[todo]) & (lam[todo] > 0)]
                if not todo.size:
                    break
            else:
                raise RuntimeError("child draw did not settle")
            ent[dep.child] = pick
    score, over, nt = _score(wn, tree, [wn.edge[x] for x in ent],
                                    lmax, windows_corrected)
    spans["pair"], spans["listed"] = nt
    return Draw(W=W, x=W * score,
                overflow=float(over.mean()) if k else 0.0, spans=spans)


def _score(wn: Windows, tree: Tree, E: list, lmax: int,
           windows_corrected: bool):
    """Per sample: completions over windows with the cap, whether a list
    ran past ``lmax``, and the non-tree lists' sizes."""
    g, delta, wd = wn.g, wn.delta, wn.wd
    motif = tree.motif
    nv = 1 + max(max(e) for e in motif)
    k = len(E[0])
    phi = np.full((k, nv), -1, np.int64)
    for s, me in enumerate(tree.edges):
        x, y = motif[me]
        phi[:, x] = g.src[E[s]]
        phi[:, y] = g.dst[E[s]]
    srt = np.sort(phi, axis=1)
    ok = np.all(srt[:, 1:] != srt[:, :-1], axis=1)
    ts = np.stack([g.t[E[s]] for s in range(len(E))], axis=1)
    tmin, tmax = ts.min(axis=1), ts.max(axis=1)
    ok &= (tmax - tmin) <= delta
    by_rank = ts[:, np.argsort(tree.edges)]
    ok &= np.all(by_rank[:, 1:] > by_rank[:, :-1], axis=1)
    nphi = np.clip(np.minimum(wn.q - 1, tmin // wd)
                   - np.maximum(0, tmax // wd - 1) + 1, 1, 2)
    if not windows_corrected:
        nphi = np.ones_like(nphi)
    cnt, over, nt = _completions(g, tree, phi, ts, ok, delta, lmax)
    over &= ok
    return np.where(ok & ~over, cnt, 0) / nphi, over, nt


def _completions(g: Graph, tree: Tree, phi, ts, ok, delta: int,
                 lmax: int):
    """Number of ways to map the non-tree edges (Alg. 5), whether a
    candidate list ran past ``lmax``, and per non-tree edge the size of
    its pair's edge list and of the candidate range."""
    motif = tree.motif
    k = len(phi)
    pins = sorted(tree.edges)
    nt = [r for r in range(len(motif)) if r not in pins]
    if not nt:
        return np.ones(k, np.int64), np.zeros(k, bool), ([], [])
    col = {me: s for s, me in enumerate(tree.edges)}
    t_first, t_last = ts[:, col[pins[0]]], ts[:, col[pins[-1]]]
    lists = []
    for r in nt:
        x, y = motif[r]
        lo = t_last - delta
        hi = t_first + delta
        below = [p for p in pins if p < r]
        above = [p for p in pins if p > r]
        if below:
            lo = np.maximum(lo, ts[:, col[below[-1]]] + 1)
        if above:
            hi = np.minimum(hi, ts[:, col[above[0]]] - 1)
        lists.append((g.pair_of(phi[:, x], phi[:, y]), lo, hi))
    lens = np.stack([g.count_between(p, lo, hi) for p, lo, hi in lists],
                    axis=1)
    sizes = [g.count_between(p, 0, g.span) for p, _, _ in lists]
    over = np.any(lens > lmax, axis=1)
    if len(nt) == 1:
        return lens[:, 0], over, (sizes, list(lens.T))
    cnt = np.zeros(k, np.int64)
    coupled = nt[0] == 0 and nt[-1] == len(motif) - 1
    for i in np.nonzero(ok & np.all(lens > 0, axis=1))[0]:
        cand = [g.times_between(int(p[i]), int(lo[i]), int(hi[i]))
                for p, lo, hi in lists]
        cnt[i] = _chains(cand, delta if coupled else None)
    return cnt, over, (sizes, list(lens.T))


def _chains(cand: list, span) -> int:
    """Strictly increasing choices, one time from each list in order;
    with ``span``, the last at most ``span`` after the first."""
    total = 0
    firsts = cand[0] if span is not None else [None]
    for f in firsts:
        ways = {int(f): 1} if f is not None else {int(x): 1 for x in cand[0]}
        for lst in cand[1:]:
            nxt = {}
            for x in lst:
                if f is not None and x > f + span:
                    continue
                nxt[int(x)] = sum(c for tt, c in ways.items() if tt < x)
            ways = nxt
        total += sum(ways.values())
    return total


@dataclass
class MotifReference:
    """What the check needs for one standing (motif, delta)."""
    trees: list       # rooted trees, Alg. 7's leading candidates first
    wn: Windows
    W: dict = None    # W per rooted-tree shape computed so far
    w: dict = None    # entry weights per shape computed so far

    def __post_init__(self):
        self.W, self.w = {}, {}

    def match(self, W_served: int) -> list:
        """The rooted trees whose W equals ``W_served``: W is computed
        shape by shape, candidates first, until one matches; every tree
        of the matching shape is returned ([] when none matches)."""
        for tree in self.trees:
            if tree.shape not in self.W:
                w = weights(self.wn, tree)
                self.W[tree.shape] = int(np.sum(w[tree.root]))
                self.w[tree.shape] = w
            if self.W[tree.shape] == W_served:
                return [t for t in self.trees if t.shape == tree.shape]
        return []

    def nearest_gap(self, W_served: int) -> float:
        """Relative distance of ``W_served`` to the nearest W computed."""
        return min(abs(W_served - W) / max(abs(W), 1)
                   for W in self.W.values())


def reference_for(g: Graph, motif: tuple, delta: int) -> MotifReference:
    return MotifReference(trees=rooted_trees(motif), wn=g.windows(delta))
