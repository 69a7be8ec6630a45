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
from repro.core.gtrace import mine_gtrace
from repro.core.reverse_search import mine_gtrace_rs
from repro.mining.driver import AcceleratedMiner
from repro.mining.encoding import (
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
        # children order-insensitively; embedding lists as sets
        assert {c: (g, set(e)) for c, g, e in got} == \
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
