"""Shard-by-pattern / shard-by-subtree serving over a device mesh.

Mirrors mining/distributed.py's layout: query sequences shard over the
"data" axis, the pattern bank (step programs + metadata rows) shards
over the "model" axis.  Containment cells are embarrassingly parallel -
cell (b, p) touches only sequence b and pattern p - so the step needs
*zero* collectives: each device computes its [B_loc, P_loc] block and
the output is the [B, P] matrix sharded over both axes (gather it, or
feed it sharded into downstream scoring).

Flat banks shard by pattern row (``make_serving_step``): rows must
divide the pattern axis; compile with ``pad_patterns_to`` a multiple of
the mesh's model-axis size (padding rows report no containment).

Trie banks shard by *subtree* (``make_trie_serving_step``): splitting a
trie by pattern row would tear shared prefixes apart and re-replicate
their work, so ``TrieBank.shard`` partitions the root's depth-1
subtrees across shards (greedy node-count balancing) and every shard
joins its own intact sub-trie.  ``stack_trie_shards`` pads the shard
tries to a common (depth, level width, pattern rows) and concatenates
them along the node/pattern axes; the step's output columns follow the
concatenated shard pattern order (``patterns`` in the stack), not the
original bank order.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P

from .batch import batch_contains_ref, trie_contains_ref
from .trie import TrieBank


def make_serving_step(
    mesh: Mesh,
    *,
    nv: int,
    n_label_keys: int,
    emax: int = 8,
    tmax: int = 16,
    db_axis: str = "data",
    pat_axis: str = "model",
    use_kernel: bool = False,
    block_g: int = 64,
):
    """Build the jitted, shard-mapped containment step.

    Returns ``step(tokens [B,T,6], steps [P,L,F], pattern_valid [P]) ->
    (contained [B,P] bool, overflow [B,P] bool)`` with B sharded over
    ``db_axis`` and P over ``pat_axis``.
    """

    def local_step(tokens, steps, pattern_valid):
        return batch_contains_ref(
            tokens, steps, pattern_valid,
            nv=nv, n_label_keys=n_label_keys, emax=emax, tmax=tmax,
            use_kernel=use_kernel, block_g=block_g,
        )

    specs_in = (
        P(db_axis, None, None),   # tokens
        P(pat_axis, None, None),  # steps
        P(pat_axis),              # pattern_valid
    )
    specs_out = (P(db_axis, pat_axis), P(db_axis, pat_axis))
    step = jax.shard_map(local_step, mesh=mesh, in_specs=specs_in,
                         out_specs=specs_out, check_vma=False)
    return jax.jit(step)


def stack_trie_shards(shards: List[TrieBank]) -> Dict[str, object]:
    """Pad shard tries to common shapes and concatenate for the mesh.

    Returns arrays keyed ``lvl_steps`` [D, S*Mh, F], ``lvl_parent_pos``
    [D, S*Mh], ``term_level``/``term_pos``/``pattern_valid`` [S*Pl]
    (term positions stay shard-local - exactly what each device's local
    [D, Mh] block indexes), plus ``patterns`` (the concatenated pattern
    list, output-column order) and ``rows_per_shard`` = Pl."""
    S = len(shards)
    D = max(max(t.depth, 1) for t in shards)
    Mh = max(
        max((len(lv) for lv in t.levels), default=1) for t in shards
    )
    Pl = max(t.bank.n_rows for t in shards)
    steps, parent_pos = [], []
    term_level, term_pos, pvalid = [], [], []
    patterns = []
    for t in shards:
        lv = t.padded_levels(depth=D, width=Mh)
        steps.append(lv.steps)
        parent_pos.append(lv.parent_pos)
        pad = Pl - t.bank.n_rows
        term_level.append(np.pad(lv.term_level, (0, pad)))
        term_pos.append(np.pad(lv.term_pos, (0, pad)))
        pvalid.append(np.pad(t.bank.pattern_valid, (0, pad)))
        patterns.append(t.bank.patterns)
    return {
        "lvl_steps": np.concatenate(steps, axis=1),
        "lvl_parent_pos": np.concatenate(parent_pos, axis=1),
        "term_level": np.concatenate(term_level),
        "term_pos": np.concatenate(term_pos),
        "pattern_valid": np.concatenate(pvalid),
        "patterns": patterns,
        "rows_per_shard": Pl,
        "n_shards": S,
    }


def make_trie_serving_step(
    mesh: Mesh,
    *,
    nv: int,
    n_label_keys: int,
    emax: int = 8,
    tmax: int = 16,
    db_axis: str = "data",
    pat_axis: str = "model",
    use_kernel: bool = False,
    block_g: int = 64,
):
    """The trie counterpart of ``make_serving_step``: each device joins
    one intact sub-trie (see ``stack_trie_shards``) against its local
    sequence block - still zero collectives.

    Returns ``step(tokens [B,T,6], lvl_steps [D,S*Mh,F],
    lvl_parent_pos [D,S*Mh], term_level [P], term_pos [P],
    pattern_valid [P]) -> (contained [B,P] bool, overflow [B,P] bool)``
    with B sharded over ``db_axis`` and the node/pattern axes over
    ``pat_axis``."""

    def local_step(tokens, lvl_steps, lvl_parent_pos, term_level,
                   term_pos, pattern_valid):
        return trie_contains_ref(
            tokens, lvl_steps, lvl_parent_pos, term_level, term_pos,
            pattern_valid,
            nv=nv, n_label_keys=n_label_keys, emax=emax, tmax=tmax,
            use_kernel=use_kernel, block_g=block_g,
        )

    specs_in = (
        P(db_axis, None, None),    # tokens
        P(None, pat_axis, None),   # lvl_steps (nodes shard)
        P(None, pat_axis),         # lvl_parent_pos
        P(pat_axis),               # term_level
        P(pat_axis),               # term_pos
        P(pat_axis),               # pattern_valid
    )
    specs_out = (P(db_axis, pat_axis), P(db_axis, pat_axis))
    step = jax.shard_map(local_step, mesh=mesh, in_specs=specs_in,
                         out_specs=specs_out, check_vma=False)
    return jax.jit(step)
