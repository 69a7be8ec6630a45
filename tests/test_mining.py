"""Device-engine tests: the accelerated miner must agree bit-for-bit with
the pure-host reference, and the fixed-size device candidate table must
agree with the exact host aggregation."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dep: seeded-sampling fallback
    from hypothesis_compat import given, settings, strategies as st

import jax.numpy as jnp

from conftest import random_db
from repro.core.canonical import canonical_form, canonical_map, iso_invariant
from repro.core.enumerate_host import apply_extension
from repro.core.graphseq import (
    TRType, edge_tr, pattern_from_lists, pattern_vertices,
)
from repro.core.gtrace import mine_gtrace
from repro.core.reverse_search import mine_gtrace_rs
from repro.mining.driver import AcceleratedMiner
from repro.mining.encoding import (
    PAD_PHI,
    PAD_PSI,
    decode_embeddings,
    encode_db,
    encode_embeddings,
    encode_pattern_trs,
    pack_signature,
    signature_to_extkey,
    unpack_signature,
)
from repro.mining.engine import (
    MODE_ROOT,
    aggregate_host,
    candidate_table_device,
    match_signatures,
)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), sigma=st.integers(2, 3))
def test_accelerated_rs_equals_core(seed, sigma):
    db = random_db(seed, n_seq=6, n_steps=4, n_v=4)
    core = mine_gtrace_rs(db, sigma, max_len=4)
    dev = AcceleratedMiner(db).mine_rs(sigma, max_len=4)
    assert core.patterns == dev.patterns


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_accelerated_gtrace_equals_core(seed):
    db = random_db(seed, n_seq=6, n_steps=4, n_v=4)
    core = mine_gtrace(db, 2, max_len=4)
    dev = AcceleratedMiner(db).mine_gtrace(2, max_len=4)
    assert core.patterns == dev.patterns


@settings(max_examples=30, deadline=None)
@given(
    slot_kind=st.integers(0, 1),
    slot_idx=st.integers(0, 15),
    ty=st.integers(0, 5),
    pu1=st.integers(0, 13),
    pu2=st.integers(0, 15),
    label=st.integers(-1, 1000),
)
def test_signature_pack_roundtrip(slot_kind, slot_idx, ty, pu1, pu2, label):
    sig = pack_signature(slot_kind, slot_idx, ty, pu1, pu2, label)
    assert 0 <= sig < 2**31
    assert unpack_signature(sig) == (slot_kind, slot_idx, ty, pu1, pu2, label)


def test_device_candidate_table_matches_host():
    db = random_db(5, n_seq=8, n_steps=5, n_v=5)
    tdb = encode_db(db)
    embs = [(g, (), ()) for g in range(len(db))]
    gid, phi, psi = encode_embeddings(embs, 8, 8)
    valid = np.ones((len(embs),), np.int32)
    existing = encode_pattern_trs((), 16)
    sigs = match_signatures(
        jnp.asarray(tdb.tokens), jnp.asarray(gid), jnp.asarray(phi),
        jnp.asarray(psi), jnp.asarray(valid), jnp.asarray(existing),
        jnp.int32(0), jnp.int32(0), jnp.int32(MODE_ROOT),
    )
    host = aggregate_host(np.asarray(sigs), gid)
    uniq, counts = candidate_table_device(sigs, jnp.asarray(gid), k=512)
    dev = {
        int(s): int(c)
        for s, c in zip(np.asarray(uniq), np.asarray(counts))
        if s >= 0
    }
    host_counts = {s: len(gs) for s, (gs, _) in host.items()}
    assert dev == host_counts


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), sigma=st.integers(2, 3),
       rs=st.booleans())
def test_wavefront_equals_pattern_dispatch(seed, sigma, rs):
    """The wavefront scheduler (frontier-batched device scans) must be
    bit-equal to the seed one-pattern-at-a-time stack miner in both
    search modes, while issuing no more device dispatches."""
    db = random_db(seed, n_seq=6, n_steps=4, n_v=4)
    wf = AcceleratedMiner(db)
    pp = AcceleratedMiner(db, dispatch="pattern")
    if rs:
        a, b = wf.mine_rs(sigma, max_len=4), pp.mine_rs(sigma, max_len=4)
    else:
        a, b = (wf.mine_gtrace(sigma, max_len=4),
                pp.mine_gtrace(sigma, max_len=4))
    assert a.patterns == b.patterns
    assert wf.n_device_calls <= pp.n_device_calls


def test_wavefront_batches_device_calls():
    """On a DB with a real pattern population the wavefront must pack
    many patterns per dispatch (the whole point)."""
    db = random_db(5, n_seq=10, n_steps=5, n_v=5)
    wf = AcceleratedMiner(db)
    pp = AcceleratedMiner(db, dispatch="pattern")
    assert wf.mine_rs(2, max_len=4).patterns == \
        pp.mine_rs(2, max_len=4).patterns
    assert pp.n_device_calls >= 5 * wf.n_device_calls, (
        wf.n_device_calls, pp.n_device_calls)


def test_expand_children_batch_matches_single():
    """A batched slice answers exactly what the per-item calls would."""
    db = random_db(9, n_seq=8, n_steps=5, n_v=5)
    m = AcceleratedMiner(db)
    roots = m.expand_children((), [(g, (), ()) for g in range(len(db))], 2)
    items = [(child, embs) for child, _, embs in roots]
    batched = m.expand_children_batch(items, 2)
    for (pattern, embs), got in zip(items, batched):
        want = AcceleratedMiner(db).expand_children(pattern, embs, 2)
        # chunk packing may reorder signature discovery, so compare
        # children order-insensitively; the batch's encoded embeddings
        # decoded, and embedding lists as sets
        assert {c: (g, set(decode_embeddings(e))) for c, g, e in got} == \
            {c: (g, set(e)) for c, g, e in want}


def test_device_seconds_includes_execution():
    """dispatch_seconds times the async launch only; device_seconds
    blocks until the result is ready, so it can never be smaller."""
    db = random_db(2, n_seq=6, n_steps=4, n_v=4)
    m = AcceleratedMiner(db)
    m.mine_rs(2, max_len=3)
    assert m.n_device_calls > 0
    assert m.device_seconds >= m.dispatch_seconds > 0.0


def test_checkpoint_resume_mid_wavefront(tmp_path):
    """Interrupting the wavefront miner at a mid-run checkpoint and
    resuming must reproduce the uninterrupted result bit-for-bit (a
    wavefront is just a reordered stack)."""
    from repro.mining import checkpoint as ckpt

    db = random_db(17, n_seq=8, n_steps=5, n_v=5)
    full = AcceleratedMiner(db).mine_rs(2, max_len=5)

    class Stop(Exception):
        pass

    ck = str(tmp_path / "wave.ckpt")
    calls = {"n": 0}
    orig = ckpt.save_state

    def capture(path, patterns, stack, meta=None):
        orig(path, patterns, stack, meta)
        calls["n"] += 1
        if calls["n"] == 1 and stack:
            raise Stop

    # wave_patterns=1 forces several slices -> a genuinely mid-wavefront
    # checkpoint with pending items from more than one wave
    m = AcceleratedMiner(db, wave_patterns=1)
    ckpt.save_state = capture
    try:
        with pytest.raises(Stop):
            m._mine(2, 5, rs=True, checkpoint_path=ck, checkpoint_every=1)
    finally:
        ckpt.save_state = orig
    resumed = AcceleratedMiner(db)._mine(
        2, 5, rs=True, checkpoint_path=ck, resume=True
    )
    assert resumed.patterns == full.patterns


def test_checkpoint_resume_equivalence(tmp_path):
    db = random_db(11, n_seq=8, n_steps=5, n_v=5)
    full = AcceleratedMiner(db).mine_rs(2, max_len=5)

    # run with aggressive checkpointing, then resume from a mid checkpoint
    ck = str(tmp_path / "mine.ckpt")
    m = AcceleratedMiner(db)
    partial_stop = {"n": 0}

    # monkeypatch save to capture an early state, then interrupt
    from repro.mining import checkpoint as ckpt

    class Stop(Exception):
        pass

    orig = ckpt.save_state
    def capture(path, patterns, stack, meta=None):
        orig(path, patterns, stack, meta)
        partial_stop["n"] += 1
        if partial_stop["n"] == 1 and stack:
            raise Stop

    import repro.mining.driver as drv
    try:
        m._mine(2, 5, rs=True, checkpoint_path=ck, checkpoint_every=3)
    except Exception:
        pass
    # checkpoint written mid-run by checkpoint_every; now interrupt harder
    m2 = AcceleratedMiner(db)
    ckpt_save, ckpt.save_state = ckpt.save_state, capture
    try:
        with pytest.raises(Stop):
            m2._mine(2, 5, rs=True, checkpoint_path=ck, checkpoint_every=2)
    finally:
        ckpt.save_state = ckpt_save
    resumed = AcceleratedMiner(db)._mine(
        2, 5, rs=True, checkpoint_path=ck, resume=True
    )
    assert resumed.patterns == full.patterns


def test_checkpoint_roundtrip(tmp_path):
    from repro.mining.checkpoint import load_state, save_state

    db = random_db(1, n_seq=4)
    res = AcceleratedMiner(db).mine_rs(2, max_len=3)
    path = str(tmp_path / "state.ckpt")
    stack = [(p, [(0, (0,), ((0, 3),))]) for p in list(res.patterns)[:2]]
    save_state(path, res.patterns, stack, meta={"x": 1})
    patterns, stack2, meta = load_state(path)
    assert patterns == res.patterns
    assert stack2 == stack
    assert meta == {"x": 1}


def test_reverse_search_funnel_counters():
    """Every distinct canonical child is counted once and leaves the
    funnel one way: below sigma, rejected by the reverse-search
    parent test, or kept as a frequent pattern."""
    db = random_db(2, n_seq=8, n_steps=4, n_v=4)
    m = AcceleratedMiner(db)
    res = m.mine_rs(2, max_len=4)
    snap = m.metrics.snapshot("mining")
    cand = snap["mining.candidates"]
    assert cand == (snap["mining.infrequent"] + snap["mining.rs_rejected"]
                    + res.n_enumerated)
    assert snap["mining.rs_rejected"] > 0
    assert snap["mining.infrequent"] > 0
    # the support-bound prescreen engaged: some signatures skipped
    # their canonical form, never more than passed the capacity guard
    assert 0 < snap["mining.canon_skipped"] <= snap["mining.signatures"]


def _ei_sig(u1, u2, label=1):
    """Signature of an edge insert into the pattern's first itemset."""
    return pack_signature(0, 0, int(TRType.EI), u1, u2, label)


@pytest.mark.parametrize("sigma", [2, 3])
def test_prescreen_keeps_children_that_share_an_invariant(sigma):
    """Two paths of four vertices: closing one into a 4-cycle and
    joining the two into a path of eight give non-isomorphic children
    with one invariant (each has five edges between degree-2 vertices
    and two from a degree-1 vertex).  Their group's union reaches sigma
    3 while neither child does, so both must come out infrequent; at
    sigma 2 both are frequent and kept apart.  A third extension, in a
    group of its own below sigma, is pruned before canonicalization."""
    pattern = pattern_from_lists([[
        edge_tr(TRType.EI, a, a + 1, 1) for a in (0, 1, 2, 4, 5, 6)]])
    cycle, path, pruned = _ei_sig(0, 3), _ei_sig(3, 4), _ei_sig(3, 8)
    gids = {cycle: {0, 1}, path: {2, 3}, pruned: {4}}
    merged = {sig: (set(g), []) for sig, g in gids.items()}
    m = AcceleratedMiner(random_db(0, n_seq=5))
    out = m._children_from_merged(pattern, None, merged, sigma, False,
                                  lambda child: False)
    kids = {sig: canonical_form(apply_extension(
        pattern, signature_to_extkey(sig))) for sig in gids}
    assert kids[cycle] != kids[path]
    assert len({iso_invariant(c) for c in kids.values()}) == 2
    want = {kids[s]: gids[s] for s in (cycle, path) if len(gids[s]) >= sigma}
    assert {c: g for c, g, _ in out} == want
    # an empty Enc stands for "not rebuilt"
    assert all(len(enc[0]) == 0 for _, _, enc in out)
    assert {s: g for s, (g, _) in merged.items()} == gids  # not mutated
    snap = m.metrics.snapshot("mining")
    assert snap["mining.signatures"] == 3
    assert snap["mining.canon_skipped"] == 1
    assert snap["mining.candidates"] == 3
    assert snap["mining.infrequent"] == 3 - len(want)


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("rs", [True, False])
def test_prescreen_mines_equal_to_core(seed, rs):
    """At a sigma where the prescreen prunes some invariant groups and
    keeps others, both accelerated miners equal the host reference."""
    db = random_db(seed, n_seq=10, n_steps=4, n_v=4)
    m = AcceleratedMiner(db)
    if rs:
        core, dev = mine_gtrace_rs(db, 3, max_len=4), m.mine_rs(3, max_len=4)
    else:
        core, dev = mine_gtrace(db, 3, max_len=4), m.mine_gtrace(3, max_len=4)
    assert core.patterns == dev.patterns
    snap = m.metrics.snapshot("mining")
    assert 0 < snap["mining.canon_skipped"] < snap["mining.signatures"]


def _rebuild_as_emb_tuples(m, pattern, enc, sig, et_rows, child_raw):
    """The child-embedding rebuild as it was when the frontier carried
    ``Emb`` tuples: the same numpy ops, then one tuple per deduped row.
    The oracle for the encoded rebuild."""
    (slot_kind, slot_idx), ptr = signature_to_extkey(sig)
    nv = len(pattern_vertices(pattern))
    n_pat = len(pattern)
    vmap = canonical_map(child_raw)
    gid_all, phi_all, psi_all = enc
    et = np.concatenate(et_rows, axis=0)
    e_i, t_i = et[:, 0], et[:, 1]
    gids_r = gid_all[e_i].astype(np.int64)
    tok = m.tdb.tokens[gids_r, t_i]
    u1, u2, j = tok[:, 1], tok[:, 2], tok[:, 4]
    phi_r = phi_all[e_i]
    if slot_kind == "in":
        new_phi = phi_r[:, :n_pat]
    else:
        new_phi = np.concatenate(
            [phi_r[:, :slot_idx], j[:, None], phi_r[:, slot_idx:n_pat]],
            axis=1)
    psi_r = psi_all[e_i][:, :nv]
    if ptr.is_vertex:
        if ptr.u1 == nv:
            psis = [np.concatenate([psi_r, u1[:, None]], axis=1)]
        else:
            psis = [psi_r]
    elif ptr.u2 == nv + 1:
        psis = [
            np.concatenate([psi_r, u1[:, None], u2[:, None]], axis=1),
            np.concatenate([psi_r, u2[:, None], u1[:, None]], axis=1),
        ]
    elif ptr.u2 == nv:
        mapped_dv = psi_r[:, ptr.u1]
        fresh_dv = np.where(mapped_dv == u1, u2, u1)
        psis = [np.concatenate([psi_r, fresh_dv[:, None]], axis=1)]
    else:
        psis = [psi_r]
    nv_child = psis[0].shape[1]
    perm = np.asarray([vmap[pv] for pv in range(nv_child)])
    n_phi = new_phi.shape[1]
    variants = []
    for ps in psis:
        canon = np.empty_like(ps)
        canon[:, perm] = ps
        variants.append(np.concatenate(
            [gids_r[:, None], new_phi, canon], axis=1))
    if len(variants) == 2:
        rows = np.stack(variants, axis=1).reshape(
            2 * len(et), 1 + n_phi + nv_child)
    else:
        rows = variants[0]
    _, first = np.unique(rows, axis=0, return_index=True)
    rows = rows[np.sort(first)]
    return [
        (
            int(r[0]),
            tuple(int(x) for x in r[1:1 + n_phi]),
            tuple(enumerate(int(x) for x in r[1 + n_phi:])),
        )
        for r in rows
    ]


@pytest.mark.parametrize("seed,rs", [(3, True), (9, True), (17, True),
                                     (5, False)])
def test_rebuild_encodes_the_tuple_rebuild(seed, rs):
    """Every rebuild of a mine returns, bit for bit and row for row,
    ``encode_embeddings`` of the ``Emb`` tuples the tuple rebuild
    made from the same inputs."""
    db = random_db(seed, n_seq=8, n_steps=5, n_v=5)
    m = AcceleratedMiner(db)
    calls = []
    rebuild = m._rebuild_embeddings

    def capture(*args):
        out = rebuild(*args)
        calls.append((args, out))
        return out

    m._rebuild_embeddings = capture
    m._mine(2, 5, rs=rs)
    assert len(calls) > 10
    for args, got in calls:
        want = encode_embeddings(
            _rebuild_as_emb_tuples(m, *args), m.ni, m.nv)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert np.array_equal(a, b)


@pytest.mark.parametrize("seed", range(4))
def test_decode_inverts_encode(seed):
    """``decode_embeddings`` is the exact inverse of
    ``encode_embeddings``: the root's empty phi and psi, full rows and
    every length between, as Python ints (the checkpoint writes them)."""
    rng = np.random.default_rng(seed)
    ni, nv = 8, 6
    embs = [(g, (), ()) for g in range(5)]
    for _ in range(60):
        phi = tuple(sorted(
            rng.choice(40, int(rng.integers(0, ni + 1)), replace=False)
            .tolist()))
        psi = tuple(enumerate(
            rng.choice(30, int(rng.integers(0, nv + 1)), replace=False)
            .tolist()))
        embs.append((int(rng.integers(0, 1000)), phi, psi))
    enc = encode_embeddings(embs, ni, nv)
    back = decode_embeddings(enc)
    assert back == embs
    assert all(type(x) is int
               for g, ph, ps in back for x in (g, *ph, *sum(ps, ())))
    assert decode_embeddings(encode_embeddings([], ni, nv)) == []


def test_root_item_is_the_encoded_root():
    """The miner's encoded root is ``encode_embeddings`` of the root's
    ``Emb`` list (every sequence, empty phi and psi)."""
    db = random_db(4, n_seq=7)
    m = AcceleratedMiner(db)
    root, enc = m._root_item()
    embs = [(g, (), ()) for g in range(len(db))]
    assert root == ()
    for a, b in zip(enc, encode_embeddings(embs, m.ni, m.nv)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert decode_embeddings(enc) == embs
    assert (enc[1] == PAD_PHI).all() and (enc[2] == PAD_PSI).all()


@pytest.mark.parametrize("seed", [4, 9, 13])
def test_expand_children_batch_takes_emb_lists_or_enc(seed):
    """A slice handed in as ``Emb`` lists expands to the same children,
    gid sets and decoded embedding sets as the same slice as ``Enc``."""
    db = random_db(seed, n_seq=8, n_steps=5, n_v=5)
    m = AcceleratedMiner(db)
    kids = m.expand_children_batch([m._root_item()], 2)[0]
    assert kids
    as_enc = [(c, e) for c, _, e in kids]
    as_embs = [(c, decode_embeddings(e)) for c, _, e in kids]

    def view(out):
        return [{c: (g, set(decode_embeddings(e))) for c, g, e in ks}
                for ks in out]

    a = view(m.expand_children_batch(as_enc, 2))
    b = view(AcceleratedMiner(db).expand_children_batch(as_embs, 2))
    assert a == b
    assert any(a)


@pytest.mark.parametrize("rs", [True, False])
def test_rebuild_rows_counts_the_kept_frontier(rs):
    """``rebuild_rows`` is the summed length of the embeddings of every
    child the expansions hand back."""
    db = random_db(2, n_seq=8, n_steps=4, n_v=4)
    m = AcceleratedMiner(db)
    total = 0
    batch = m.expand_children_batch

    def count(*args, **kw):
        nonlocal total
        out = batch(*args, **kw)
        total += sum(len(e[0]) for ks in out for _, _, e in ks)
        return out

    m.expand_children_batch = count
    m._mine(2, 4, rs=rs)
    snap = m.metrics.snapshot("mining")
    assert snap["mining.rebuild_rows"] == total > 0


@pytest.mark.parametrize("rs", [True, False])
def test_mine_never_encodes_emb_tuples(monkeypatch, rs):
    """From the root to the last wave the frontier stays encoded: a mine
    without a checkpoint never calls ``encode_embeddings``, and finds
    the host reference's patterns."""
    from repro.mining import driver

    def refuse(*args, **kw):
        raise AssertionError("encode_embeddings called inside a mine")

    monkeypatch.setattr(driver, "encode_embeddings", refuse)
    db = random_db(6, n_seq=8, n_steps=5, n_v=5)
    dev = AcceleratedMiner(db)._mine(2, 5, rs=rs)
    core = (mine_gtrace_rs if rs else mine_gtrace)(db, 2, max_len=5)
    assert dev.patterns == core.patterns
