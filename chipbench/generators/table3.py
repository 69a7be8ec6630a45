"""The artificial-data generator of GTRACE-RS (arXiv:1110.3879) section
5.1, Table 3.  It draws from ``random.Random`` in the same order as the
generator it was copied from (``repro.data.synthetic``), so the same
seed gives the same sequences."""
from __future__ import annotations

import random
from typing import List

from chipbench.lib.gen import EI, Graph, Seq, compile_graphs

TABLE3 = dict(p_i=0.80, p_d=0.10, v_avg=6, v_avg_pattern=3, n_vlabels=5,
              n_elabels=5, n_patterns=10, db_size=1000, p_e=0.15, d_ist=2,
              n_interstates=5)


def _mutate(g, rng, p_i, p_d, n_v, n_vl, n_el, p_e):
    r = rng.random()
    vs = sorted(g.v)
    if r < p_i or not vs:
        if rng.random() < 0.5 or len(vs) < 2:
            u = 0
            while u in g.v:
                u += 1
            if u >= n_v:
                return
            g.v[u] = rng.randrange(n_vl)
            for w in vs:
                if rng.random() < p_e:
                    g.add_edge(u, w, rng.randrange(n_el))
        else:
            u, w = rng.sample(vs, 2)
            e = (min(u, w), max(u, w))
            if e not in g.e:
                g.add_edge(u, w, rng.randrange(n_el))
    elif r < p_i + p_d:
        if g.e and rng.random() < 0.7:
            del g.e[rng.choice(sorted(g.e))]
        else:
            iso = [u for u in g.v if not g.incident(u)]
            if iso:
                del g.v[rng.choice(iso)]
    else:
        if g.e and rng.random() < 0.5:
            e = rng.choice(sorted(g.e))
            g.e[e] = rng.randrange(n_el)
        elif vs:
            u = rng.choice(vs)
            g.v[u] = rng.randrange(n_vl)


def _relevant(p) -> bool:
    vs = {t[1] for s in p for t in s} | {t[2] for s in p for t in s
                                          if t[0] >= EI}
    if not vs:
        return True
    root = {v: v for v in vs}

    def find(x):
        while root[x] != x:
            x = root[x]
        return x

    for s in p:
        for t in s:
            if t[0] >= EI:
                root[find(t[1])] = find(t[2])
    return len({find(v) for v in vs}) <= 1


def _pattern(rng, p):
    while True:
        g = Graph()
        graphs = []
        for _ in range(rng.randint(2, 3)):
            for _ in range(rng.randint(1, 2)):
                _mutate(g, rng, 0.85, 0.05, p["v_avg_pattern"],
                        p["n_vlabels"], p["n_elabels"], 0.5)
            graphs.append(g.copy())
        pat = tuple(frozenset(it) for it in compile_graphs(graphs) if it)
        if pat and _relevant(pat) and sum(len(i) for i in pat) >= 2:
            return pat


def _grow(rng, p):
    g = Graph()
    n_v = p["v_avg"]
    for u in range(max(1, n_v // 2)):
        g.v[u] = rng.randrange(p["n_vlabels"])
    vs = sorted(g.v)
    for i in range(len(vs)):
        for j in range(i + 1, len(vs)):
            if rng.random() < p["p_e"]:
                g.add_edge(vs[i], vs[j], rng.randrange(p["n_elabels"]))
    seq = [g.copy()]
    for _ in range(p["n_interstates"] - 1):
        for _ in range(p["d_ist"]):
            _mutate(g, rng, p["p_i"], p["p_d"], n_v, p["n_vlabels"],
                    p["n_elabels"], p["p_e"])
        seq.append(g.copy())
    return seq


def _overlay(s, pattern, rng, base):
    n = len(s)
    if n < len(pattern):
        return s
    positions = sorted(rng.sample(range(n), len(pattern)))
    vmap = {}
    out = [list(it) for it in s]
    for pos, itemset in zip(positions, pattern):
        for t in sorted(itemset):
            for v in (t[1],) if t[0] < EI else (t[1], t[2]):
                if v not in vmap:
                    vmap[v] = base + len(vmap)
            if t[0] < EI:
                nt = (t[0], vmap[t[1]], t[2], t[3])
            else:
                a, b = vmap[t[1]], vmap[t[2]]
                nt = (t[0], min(a, b), max(a, b), t[3])
            if nt not in out[pos]:
                out[pos].append(nt)
    return tuple(tuple(x) for x in out)


def generate(seed: int, **over) -> List[Seq]:
    """``db_size`` sequences of the Table 3 generator: ``n_patterns``
    random relevant patterns, then each sequence grown by
    insert/delete/relabel steps and overlaid with each pattern with
    probability 1/``n_patterns``."""
    p = dict(TABLE3, **over)
    rng = random.Random(seed)
    pats = [_pattern(rng, p) for _ in range(p["n_patterns"])]
    db = []
    for _ in range(p["db_size"]):
        s = compile_graphs(_grow(rng, p))
        for pat in pats:
            if rng.random() < 1.0 / p["n_patterns"]:
                s = _overlay(s, pat, rng, 1000)
        db.append(s)
    return db
