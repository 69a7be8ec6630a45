"""Unit + property tests for the GTRACE core layer."""
import random

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: seeded-sampling fallback
    from hypothesis_compat import given, settings, strategies as st

from conftest import random_db
from repro.core.canonical import (
    canonical_form,
    canonical_map,
    is_canonical,
    iso_invariant,
    relabel_pattern,
)
from repro.core.compile import compile_sequence, diff_graphs, reconstruct
from repro.core.containment import contains, iter_embeddings, support
from repro.core.graphseq import (
    LabeledGraph,
    TR,
    TRType,
    edge_tr,
    pattern_from_lists,
    pattern_length,
    pattern_vertices,
    vertex_tr,
)
from repro.core.union_graph import is_relevant, pattern_union_graph
from repro.data.synthetic import random_graph_sequence


# ---------------------------------------------------------------- compile
@settings(max_examples=50, deadline=None)
@given(st.integers(0, 10_000))
def test_compile_reconstruct_roundtrip(seed):
    rng = random.Random(seed)
    seq = random_graph_sequence(rng, n_steps=5, n_v=5, n_vl=3, n_el=3)
    s = compile_sequence(seq)
    rebuilt = reconstruct(s)
    assert len(rebuilt) == len(seq)
    for a, b in zip(rebuilt, seq):
        assert a == b


def test_compile_fig4_example():
    """Example 2: the Fig. 4 sequence compiles to the listed TRs."""
    A, B, C = 0, 1, 2
    g1 = LabeledGraph({1: A, 2: B, 3: A}, {(1, 3): 0, (2, 3): 0})
    g2 = g1.copy(); g2.add_vertex(4, C)
    g3 = g2.copy(); g3.add_vertex(5, C); g3.add_edge(3, 4, 0); g3.remove_edge(2, 3)
    g4 = g3.copy(); g4.remove_edge(1, 3); g4.remove_vertex(2); g4.remove_vertex(1)
    s = compile_sequence([g1, g2, g3, g4], encode_initial=False)
    assert s[0] == (vertex_tr(TRType.VI, 4, C),)
    assert set(s[1]) == {
        vertex_tr(TRType.VI, 5, C),
        edge_tr(TRType.EI, 3, 4, 0),
        edge_tr(TRType.ED, 2, 3),
    }
    assert set(s[2]) == {
        vertex_tr(TRType.VD, 1),
        vertex_tr(TRType.VD, 2),
        edge_tr(TRType.ED, 1, 3),
    }


def test_diff_is_minimal():
    g0 = LabeledGraph({1: 0, 2: 1}, {(1, 2): 0})
    g1 = LabeledGraph({1: 0, 2: 1}, {(1, 2): 0})
    assert diff_graphs(g0, g1) == []
    g1.vlabels[2] = 0
    assert len(diff_graphs(g0, g1)) == 1


# ------------------------------------------------------------- containment
def test_containment_example3():
    """Example 3 (itemset-sequence semantics; see DESIGN.md note)."""
    C = 2
    s_d = (
        (vertex_tr(TRType.VI, 4, C),),
        (vertex_tr(TRType.VI, 5, C), edge_tr(TRType.EI, 3, 4, 0),
         edge_tr(TRType.ED, 2, 3)),
        (vertex_tr(TRType.VD, 2), edge_tr(TRType.ED, 1, 3)),
    )
    s_p = pattern_from_lists([
        [vertex_tr(TRType.VI, 3, C)],
        [edge_tr(TRType.EI, 2, 3, 0), edge_tr(TRType.ED, 1, 2)],
        [vertex_tr(TRType.VD, 1)],
    ])
    assert contains(s_p, s_d)
    embs = list(iter_embeddings(s_p, s_d))
    # psi(i) = i+1 with phi = (0, 1, 2) must be among the embeddings
    assert any(
        dict(psi) == {1: 2, 2: 3, 3: 4} and phi == (0, 1, 2)
        for phi, psi in embs
    )


def test_containment_requires_injective_psi():
    s_d = ((vertex_tr(TRType.VI, 1, 0),), (vertex_tr(TRType.VI, 2, 0),))
    p = pattern_from_lists([[vertex_tr(TRType.VI, 1, 0)],
                            [vertex_tr(TRType.VI, 2, 0)]])
    assert contains(p, s_d)
    # two pattern vertices cannot both map to data vertex 1
    s_d2 = ((vertex_tr(TRType.VI, 1, 0),), (vertex_tr(TRType.VR, 1, 0),))
    assert not contains(p, s_d2)


def test_containment_phi_order():
    p = pattern_from_lists([[vertex_tr(TRType.VI, 1, 0)],
                            [vertex_tr(TRType.VD, 1)]])
    ok = ((vertex_tr(TRType.VI, 7, 0),), (vertex_tr(TRType.VD, 7),))
    rev = ((vertex_tr(TRType.VD, 7),), (vertex_tr(TRType.VI, 7, 0),))
    assert contains(p, ok)
    assert not contains(p, rev)


# --------------------------------------------------------------- canonical
@settings(max_examples=50, deadline=None)
@given(st.integers(0, 10_000))
def test_canonical_invariant_under_relabeling(seed):
    rng = random.Random(seed)
    db = random_db(seed, n_seq=2)
    for s in db:
        pat = pattern_from_lists([it for it in s if it])
        if not pat:
            continue
        vs = pattern_vertices(pat)
        perm = list(range(len(vs)))
        rng.shuffle(perm)
        relabeled = relabel_pattern(pat, {v: 100 + perm[i] for i, v in enumerate(vs)})
        assert canonical_form(pat) == canonical_form(relabeled)


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 10_000))
def test_iso_invariant_under_relabeling(seed):
    """Relabelings share the invariant, as they share canonical forms."""
    rng = random.Random(seed)
    db = random_db(seed, n_seq=2)
    for s in db:
        pat = pattern_from_lists([it for it in s if it])
        if not pat:
            continue
        vs = pattern_vertices(pat)
        perm = list(range(len(vs)))
        rng.shuffle(perm)
        relabeled = relabel_pattern(pat, {v: 100 + perm[i] for i, v in enumerate(vs)})
        assert iso_invariant(pat) == iso_invariant(relabeled)
        assert iso_invariant(pat) == iso_invariant(canonical_form(pat))


def test_iso_invariant_is_only_necessary():
    """A 6-cycle and two triangles of same-label edge inserts in one
    itemset: every vertex has two like edges, so the invariant cannot
    tell them apart, while their canonical forms differ."""
    def ring(cycles):
        return pattern_from_lists([[
            edge_tr(TRType.EI, c[i], c[(i + 1) % len(c)], 1)
            for c in cycles for i in range(len(c))
        ]])

    hexagon = ring([(0, 1, 2, 3, 4, 5)])
    triangles = ring([(0, 1, 2), (3, 4, 5)])
    assert iso_invariant(hexagon) == iso_invariant(triangles)
    assert canonical_form(hexagon) != canonical_form(triangles)
    # a ring of another size has another invariant
    assert iso_invariant(hexagon) != iso_invariant(ring([(0, 1, 2, 3, 4)]))


def test_canonical_idempotent_and_compact():
    p = pattern_from_lists([[edge_tr(TRType.EI, 7, 3, 1)],
                            [vertex_tr(TRType.VR, 7, 0)]])
    c = canonical_form(p)
    assert is_canonical(c)
    assert set(pattern_vertices(c)) == {0, 1}
    m = canonical_map(p)
    assert relabel_pattern(p, m) == c


# ------------------------------------------------------------- union graph
def test_relevance():
    assert is_relevant(pattern_from_lists([[vertex_tr(TRType.VI, 1, 0)]]))
    assert not is_relevant(pattern_from_lists(
        [[vertex_tr(TRType.VI, 1, 0)], [vertex_tr(TRType.VI, 2, 0)]]))
    assert is_relevant(pattern_from_lists(
        [[vertex_tr(TRType.VI, 1, 0)], [vertex_tr(TRType.VI, 2, 0)],
         [edge_tr(TRType.EI, 1, 2, 0)]]))
    # union graph of example 4: two edge TRs sharing vertex 2
    p = pattern_from_lists([[edge_tr(TRType.EI, 1, 2, 0)],
                            [edge_tr(TRType.EI, 2, 3, 0)]])
    ug = pattern_union_graph(p)
    assert ug.vertices == {1, 2, 3} and len(ug.edges) == 2
    assert is_relevant(p)


def test_pattern_length():
    p = pattern_from_lists([[edge_tr(TRType.EI, 1, 2, 0)],
                            [edge_tr(TRType.EI, 2, 3, 0),
                             edge_tr(TRType.ED, 1, 2)]])
    assert pattern_length(p) == 3
