"""The boundary to the system under test: the benchmark's plain tuples
become the program's types here, and the program's answers become plain
values again.  Nothing else in the yardstick touches the program."""
from __future__ import annotations

from pathlib import Path

from . import reference
from .harness import ROOT, load_json


def seq(s):
    """A data sequence as the program's ``TRSeq``."""
    from repro.core.graphseq import TR, TRType
    return tuple(tuple(TR(TRType(t[0]), t[1], t[2], t[3]) for t in it)
                 for it in s)


def pattern(p):
    from repro.core.graphseq import TR, TRType
    return tuple(frozenset(TR(TRType(t[0]), t[1], t[2], t[3]) for t in it)
                 for it in p)


def plain(p):
    """A program pattern (or sequence) as plain tuples."""
    return tuple(tuple((int(t.type), t.u1, t.u2, t.label) for t in it)
                 for it in p)


def mined(result) -> dict:
    """The program's mined ``{pattern: support}`` keyed by reference
    code."""
    return {reference.code(plain(p)): int(s) for p, s in result.items()}


def load_bank(config: dict) -> dict:
    """The configuration's bank file: ``{code: support}``."""
    b = load_json(Path(ROOT) / config["bank"])
    return {reference.code(tuple(tuple(map(tuple, it)) for it in c)): s
            for c, s in b["patterns"]}


def compile_bank(bank: dict):
    """The program's compiled bank of the reference patterns, and per
    bank row the reference code it holds (``None`` for a row that is
    not one of them)."""
    from repro.serving.bank import compile_bank as cb
    pb = cb({pattern(reference.from_code(c)): s for c, s in bank.items()})
    codes = [reference.code(plain(p)) for p in pb.patterns[:pb.n_patterns]]
    return pb, [c if c in bank else None for c in codes]
