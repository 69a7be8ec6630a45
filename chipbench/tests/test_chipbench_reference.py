"""The plain reference: its canonical code, its containment and its
miner, and the banks written from it."""
import random

from chipbench.lib import gen, harness, program, reference


def _rename(p, perm):
    m = dict(perm)
    return tuple(frozenset(
        (t[0], m[t[1]], -1, t[3]) if t[0] < 3 else
        (t[0], min(m[t[1]], m[t[2]]), max(m[t[1]], m[t[2]]), t[3])
        for t in it) for it in p)


def test_code_is_kept_by_renaming_and_tells_patterns_apart():
    codes = {}
    for c in list(program.load_bank(
            harness.load_json(harness.BENCH / "configs" / "table3.json")))[:60]:
        p = reference.from_code(c)
        vs = sorted({v for it in p for t in it
                     for v in ((t[1],) if t[0] < 3 else t[1:3])})
        shuffled = vs[:]
        random.Random(len(codes)).shuffle(shuffled)
        q = _rename(p, [(a, b + 10) for a, b in zip(vs, shuffled)])
        assert reference.code(q) == c
        codes[c] = p
    assert len(codes) == 60


def test_containment_agrees_with_the_program_oracle():
    from repro.core.containment import contains
    db = gen.generate("table3", 3, db_size=40)
    bank = reference.mine(db, 4, 3)
    pats = [reference.from_code(c) for c in bank]
    for s in gen.generate("table3", 4, db_size=20):
        for p in pats:
            assert reference.contains(p, s) == contains(
                program.pattern(p), program.seq(s))


def test_miner_supports_are_containment_counts():
    db = gen.generate("table3", 5, db_size=30)
    got = reference.mine(db, 3, 3)
    assert got
    for c, s in list(got.items())[:40]:
        p = reference.from_code(c)
        assert s == sum(reference.contains(p, x) for x in db)


def _bank_file_agrees(name):
    cfg = harness.load_json(harness.BENCH / "configs" / f"{name}.json")
    db = gen.database(cfg, cfg["data"]["seed"])
    want = reference.mine(db, cfg["sigma"], cfg["max_len"])
    assert program.load_bank(cfg) == want
    return db, cfg, want


def test_table3_bank_file_is_the_reference_and_program_mining():
    from repro.core.reverse_search import mine_gtrace_rs
    db, cfg, want = _bank_file_agrees("table3")
    assert len(want) == 211
    got = mine_gtrace_rs([program.seq(s) for s in db], cfg["sigma"],
                         max_len=cfg["max_len"]).patterns
    assert program.mined(got) == want
