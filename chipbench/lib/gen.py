"""The benchmark's own data generators, kept apart from the program so
that no change to the program can change the data it is measured on.

A transformation rule (TR) here is a plain tuple ``(type, u1, u2,
label)``: type 0..5 is vi, vd, vr, ei, ed, er; ``u2 == -1`` for vertex
rules; ``label == -1`` for deletions; edge endpoints have ``u1 < u2``.
A data sequence is a tuple of itemsets, each a tuple of TRs.

Each generator is a file of its own, ``chipbench/generators/<name>.py``,
with ``generate(seed, **params)``; a configuration names it under
``data.generator``, and the parameter that sets its number of sequences
under ``data.size_key``.  This module holds what they share: graphs and
their edit scripts.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

VI, VD, VR, EI, ED, ER = range(6)
NO = -1

TR = Tuple[int, int, int, int]
Seq = Tuple[Tuple[TR, ...], ...]

class Graph:
    """Labeled undirected graph with persistent vertex ids."""

    __slots__ = ("v", "e")

    def __init__(self):
        self.v: Dict[int, int] = {}
        self.e: Dict[Tuple[int, int], int] = {}

    def add_edge(self, u, w, lab):
        self.e[(min(u, w), max(u, w))] = lab

    def incident(self, u):
        return [e for e in self.e if u in e]

    def copy(self):
        g = Graph()
        g.v, g.e = dict(self.v), dict(self.e)
        return g


def diff(g0: Graph, g1: Graph) -> List[TR]:
    """The minimal applicable edit script from ``g0`` to ``g1``:
    relabels, edge deletions, vertex deletions, vertex insertions, edge
    insertions."""
    out: List[TR] = []
    for u in sorted(g0.v.keys() & g1.v.keys()):
        if g0.v[u] != g1.v[u]:
            out.append((VR, u, NO, g1.v[u]))
    for e in sorted(g0.e.keys() & g1.e.keys()):
        if g0.e[e] != g1.e[e]:
            out.append((ER, e[0], e[1], g1.e[e]))
    out += [(ED, a, b, NO) for a, b in sorted(g0.e.keys() - g1.e.keys())]
    out += [(VD, u, NO, NO) for u in sorted(g0.v.keys() - g1.v.keys())]
    out += [(VI, u, NO, g1.v[u]) for u in sorted(g1.v.keys() - g0.v.keys())]
    out += [(EI, a, b, g1.e[(a, b)])
            for a, b in sorted(g1.e.keys() - g0.e.keys())]
    return out


def compile_graphs(graphs: List[Graph]) -> Seq:
    """A graph sequence as its sequence of edit scripts, the first one
    building ``graphs[0]`` from the empty graph."""
    gs = [Graph()] + list(graphs)
    return tuple(tuple(diff(a, b)) for a, b in zip(gs, gs[1:]))


def generate(name: str, seed: int, **params) -> List[Seq]:
    """The sequences of generator ``name`` for ``seed``."""
    from .harness import load_module
    return load_module("generators", name).generate(seed, **params)


def database(config: dict, seed: int, n: int | None = None) -> List[Seq]:
    """The configuration's database drawn from ``seed``; with ``n``, at
    ``n`` sequences instead of the configuration's size."""
    data = config["data"]
    params = dict(data["params"])
    if n is not None:
        params[data["size_key"]] = n
    return generate(data["generator"], seed, **params)
