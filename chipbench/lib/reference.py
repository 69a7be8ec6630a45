"""Plain reference for GTRACE-RS answers: containment and mining of
transformation-sequence patterns, written from the definitions of
arXiv:1110.3879 (Defs 4-7) and importing nothing of the program.

Data and patterns use ``gen``'s plain tuples: a TR is ``(type, u1, u2,
label)`` (types 0..2 vertex rules with ``u2 == -1``, 3..5 edge rules
with ``u1 < u2``); a pattern is a tuple of non-empty itemsets.

* ``contains(p, s)`` (Def 4): there are a strictly increasing map of
  pattern itemsets to data itemsets and an injective map of pattern
  vertices to data vertices under which every pattern TR has a data TR
  of the same type and label in the mapped itemset.
* ``code(p)``: a canonical code, equal for two patterns exactly when
  one is a vertex renaming of the other (Def 7 asks only for that).
* ``mine(db, sigma, max_len)``: every relevant pattern (connected union
  graph, Defs 5-6) of 1 to ``max_len`` TRs contained in at least
  ``sigma`` sequences, keyed by ``code``, with its support.  It grows
  patterns level by level, one TR at a time, from their embeddings:
  every relevant pattern of k + 1 TRs has a relevant sub-pattern of k
  TRs, and support only falls as a pattern grows, so the levels reach
  every frequent relevant pattern.
"""
from __future__ import annotations

import itertools
from collections import defaultdict
from typing import Dict, Iterator, Sequence, Tuple

EI = 3
NO = -1


def _verts(t):
    return (t[1], t[2]) if t[0] >= EI else (t[1],)


def relevant(p) -> bool:
    vs = {v for s in p for t in s for v in _verts(t)}
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


# --------------------------------------------------------------- embeddings
def _index(s):
    """Per data itemset: (type, label) -> its TRs."""
    out = []
    for items in s:
        d = defaultdict(list)
        for t in items:
            d[(t[0], t[3])].append(t)
        out.append(d)
    return out


def _match(trs, idx, psi, used) -> Iterator[dict]:
    """Extensions of ``psi`` under which every TR of ``trs`` has an
    image in the data itemset ``idx``."""
    if not trs:
        yield psi
        return
    t, rest = trs[0], trs[1:]
    for d in idx.get((t[0], t[3]), ()):
        if t[0] < EI:
            pairs = (((t[1], d[1]),),)
        else:
            pairs = (((t[1], d[1]), (t[2], d[2])),
                     ((t[1], d[2]), (t[2], d[1])))
        for pp in pairs:
            new = dict(psi)
            taken = set(used)
            ok = True
            for pv, dv in pp:
                if pv in new:
                    ok = new[pv] == dv
                elif dv in taken:
                    ok = False
                else:
                    new[pv] = dv
                    taken.add(dv)
                if not ok:
                    break
            if ok:
                yield from _match(rest, idx, new, taken)


def _order(itemset, bound):
    """TRs of an itemset, those touching bound vertices first."""
    return sorted(itemset, key=lambda t: -sum(v in bound for v in _verts(t)))


def embeddings(p, s, idx=None) -> Iterator[Tuple[Tuple[int, ...], dict]]:
    """Every embedding ``(phi, psi)`` of pattern ``p`` in sequence ``s``."""
    idx = _index(s) if idx is None else idx
    n = len(p)

    def rec(i, start, psi, phi):
        if i == n:
            yield tuple(phi), psi
            return
        trs = _order(p[i], psi)
        for j in range(start, len(s) - (n - i - 1)):
            for new in _match(trs, idx[j], psi, set(psi.values())):
                phi.append(j)
                yield from rec(i + 1, j + 1, new, phi)
                phi.pop()

    yield from rec(0, 0, {}, [])


def contains(p, s, idx=None) -> bool:
    for _ in embeddings(p, s, idx):
        return True
    return False


# ---------------------------------------------------------------- canonical
def _enc(t, m):
    if t[0] < EI:
        return (t[0], m[t[1]], NO, t[3])
    a, b = m[t[1]], m[t[2]]
    return (t[0], min(a, b), max(a, b), t[3])


def code(p) -> tuple:
    """The least encoding of ``p`` over the vertex numberings that list
    vertices in the order of a renaming-invariant signature (each
    vertex's TRs by itemset, type and label).  Both the signature order
    and the least encoding are kept by every renaming, so the code is
    canonical; it determines ``p`` up to renaming, so it is complete."""
    sig = defaultdict(list)
    for i, s in enumerate(p):
        for t in s:
            for k, v in enumerate(_verts(t)):
                sig[v].append((i, t[0], t[3], k if t[0] < EI else 2))
    classes = defaultdict(list)
    for v, x in sig.items():
        classes[tuple(sorted(x))].append(v)
    blocks = [classes[k] for k in sorted(classes)]
    best = None
    for perms in itertools.product(
            *(itertools.permutations(b) for b in blocks)):
        m, nxt = {}, 0
        for perm in perms:
            for v in perm:
                m[v] = nxt
                nxt += 1
        c = tuple(tuple(sorted(_enc(t, m) for t in s)) for s in p)
        if best is None or c < best:
            best = c
    return best if best is not None else ()


def from_code(c) -> tuple:
    return tuple(frozenset(s) for s in c)


# ------------------------------------------------------------------- mining
def _extensions(p, s, idx, phi, psi, out: set) -> None:
    """The one-TR extensions of ``p`` that this embedding extends to in
    ``s``, as written (not yet canonical)."""
    inv = {dv: pv for pv, dv in psi.items()}
    fresh0 = max(psi, default=-1) + 1
    at = {j: i for i, j in enumerate(phi)}
    for j, items in enumerate(s):
        if j in at:
            image = {_enc(t, psi) for t in p[at[j]]}
        for d in items:
            if j in at and d in image:
                continue
            m, nxt = {}, fresh0
            for dv in _verts(d):
                if dv in inv:
                    m[dv] = inv[dv]
                else:
                    m[dv] = nxt
                    nxt += 1
            t = _enc(d, m)
            if j in at:
                i = at[j]
                q = p[:i] + (p[i] | {t},) + p[i + 1:]
            else:
                i = sum(1 for x in phi if x < j)
                q = p[:i] + (frozenset((t,)),) + p[i:]
            out.add(q)


def mine(db: Sequence, sigma: int, max_len: int) -> Dict[tuple, int]:
    """Every frequent relevant pattern, ``{code: support}``."""
    idxs = [_index(s) for s in db]
    canon: Dict[tuple, tuple] = {}  # written pattern -> code, or None
    found: Dict[tuple, int] = {}
    level = {(): list(range(len(db)))}
    for _ in range(max_len):
        where = defaultdict(list)
        for c, gids in level.items():
            p = from_code(c)
            for g in gids:
                seen: set = set()
                for phi, psi in embeddings(p, db[g], idxs[g]):
                    _extensions(p, db[g], idxs[g], phi, psi, seen)
                codes = set()
                for q in seen:
                    if q not in canon:
                        canon[q] = code(q) if relevant(q) else None
                    codes.add(canon[q])
                codes.discard(None)
                for q in codes:
                    where[q].append(g)
        level = {}
        for q, gids in where.items():
            gids = sorted(set(gids))
            if len(gids) >= sigma:
                level[q] = gids
                found[q] = len(gids)
        if not level:
            break
    return found
