"""Pallas TPU kernel for the embedding-join match (the mining hot loop).

The computation is elementwise int32 predicate work over an
(embeddings x tokens) grid with three small per-row tables (phi, psi) and
two tiny replicated tables (existing-TR list, scalars).  It is memory
bound: ~arithmetic-intensity (NV+NI+P) int ops per 4-byte signature
written, with the per-column [bE,bT] compare planes living entirely in
VMEM/VREGs instead of HBM (the jnp reference materializes its
[E,T,NV] broadcasts to HBM on the XLA side unless fused).  The body is
``match_fields``, the reference's predicate in a form Mosaic lowers.

Tiling: grid (E/bE, T/bT); per grid step the kernel touches
  tok block   [6, bE, bT]  int32   (24*bE*bT bytes, field-major)
  phi/psi     [bE, NI], [bE, NV]
  out         [bE, bT]     int32
Defaults bE=64, bT=128 keep the working set < 1 MB of VMEM and the lane
dimension of the output a multiple of 128.

The phi/psi blocks' *lane* (last) dims are the small NI/NV statics
(typically 16/12), which a TPU would relayout to the 128-lane boundary
on every block load; ``lane_pad`` pads them up front with the inert
sentinels (PAD_PHI / PAD_PSI - both unmatched by construction, so the
signatures are unchanged).  It follows the existing backend
auto-select: on by default exactly when the kernel compiles for real
(interpret=False, i.e. on TPU), off in interpret mode where padding
only adds work - interpret-mode parity is tested by forcing it on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...mining.encoding import (
    INVALID_SIG,
    PAD_PHI,
    PAD_PSI,
    SENT_V,
    _LAB_BITS,
    _PU_BITS,
    _SL_BITS,
    _TY_BITS,
)
from .. import default_interpret
from ..containment.containment import bool_where
from .ref import (
    _BIG,
    MODE_EDGE_PHASE,
    MODE_ROOT,
    MODE_TAIL,
    MODE_VERTEX_PHASE,
)

LANE = 128


def _lane_pad_to(n: int) -> int:
    return -(-n // LANE) * LANE


def _first_match(table, x):
    """table [E,W], x [E,T] -> [E,T] int32: the smallest column w with
    ``table[e, w] == x[e, t]``, BIG when none matches."""
    pos = jnp.full(x.shape, _BIG, jnp.int32)
    for w in range(table.shape[-1] - 1, -1, -1):
        pos = jnp.where(table[:, w:w + 1] == x, w, pos)
    return pos


def match_fields(tok_f, phi, psi, emb_valid, existing, nv, n_pat, mode):
    """``ref.match_core`` (single-pattern form) in a form Mosaic lowers
    - the kernel body.  Tokens come field-major (``tok_f [6,E,T]``), so
    every field is a lane-dense ``[E,T]`` plane; ``emb_valid`` is an
    ``[E,1]`` column; the scalars and the shared ``existing [P,5]``
    table are read from SMEM (``existing[p, c]`` is a scalar load: a
    vector could not be broadcast to ``[E,T]`` in one step).  The small
    tables are walked by static loops of ``[E,1]`` / scalar broadcasts
    - no 3-D broadcast, gather or select between booleans."""
    ty, u1, u2, lab, j = (tok_f[c] for c in range(5))
    valid = tok_f[5] > 0
    is_v = ty <= 2

    pid1 = _first_match(psi, u1)
    pid2 = _first_match(psi, u2)
    m1 = pid1 < _BIG
    m2 = pid2 < _BIG
    pid1 = jnp.where(m1, pid1, nv)
    pid2 = jnp.where(m2, pid2, nv)

    # vertex-TR candidate
    ok_v = (mode == MODE_ROOT) | (mode == MODE_TAIL) | m1

    # edge-TR candidate
    both = m1 & m2
    one = m1 ^ m2
    mapped_pid = jnp.where(m1, pid1, pid2)
    a = jnp.where(both, jnp.minimum(pid1, pid2),
                  jnp.where(one, mapped_pid, nv))
    b = jnp.where(both, jnp.maximum(pid1, pid2),
                  jnp.where(one, nv, nv + 1))
    # vertex phase: no edge TRs; edge phase: >=1 mapped endpoint
    ok_e = (mode != MODE_VERTEX_PHASE) & (
        (mode != MODE_EDGE_PHASE) | m1 | m2)

    pu1 = jnp.where(is_v, pid1, a).astype(jnp.int32)
    pu2 = jnp.where(is_v, SENT_V, b).astype(jnp.int32)
    allowed = valid & bool_where(is_v, ok_v, ok_e)

    # temporal slot
    in_pos = _first_match(phi, j)
    in_any = in_pos < _BIG
    in_idx = jnp.where(in_any, in_pos, 0).astype(jnp.int32)
    gap_idx = jnp.zeros(j.shape, jnp.int32)
    for i in range(phi.shape[-1]):
        gap_idx = gap_idx + (phi[:, i:i + 1] < j).astype(jnp.int32)
    slot_kind = jnp.where(in_any, 0, 1).astype(jnp.int32)
    slot_idx = jnp.where(in_any, in_idx, gap_idx)

    tail_ok = (mode != MODE_TAIL) | (
        (in_any & (in_idx == n_pat - 1)) | (~in_any & (gap_idx == n_pat)))

    # duplicate-TR-in-itemset rejection
    dup = jnp.zeros(j.shape, jnp.bool_)
    for p in range(existing.shape[0]):
        dup = dup | (
            (existing[p, 0] == slot_idx)
            & (existing[p, 1] == ty)
            & (existing[p, 2] == pu1)
            & (existing[p, 3] == pu2)
            & (existing[p, 4] == lab)
        )
    dup = dup & in_any

    v = slot_kind
    v = (v << _SL_BITS) | slot_idx
    v = (v << _TY_BITS) | ty
    v = (v << _PU_BITS) | pu1
    v = (v << _PU_BITS) | pu2
    v = (v << _LAB_BITS) | (lab + 1)
    keep = allowed & tail_ok & ~dup & (emb_valid > 0)
    return jnp.where(keep, v, INVALID_SIG)


def _kernel(scal_ref, tok_ref, phi_ref, psi_ref, valid_ref, ex_ref,
            out_ref):
    out_ref[...] = match_fields(
        tok_ref[...],
        phi_ref[...],
        psi_ref[...],
        valid_ref[...],
        ex_ref,           # SMEM: scalar reads
        scal_ref[0, 0],
        scal_ref[0, 1],
        scal_ref[0, 2],
    )


def match_signatures_blocked(
    tok_e,       # [E, T, 6] int32 (pre-gathered per embedding)
    phi,         # [E, NI] int32
    psi,         # [E, NV] int32
    emb_valid,   # [E] int32
    existing,    # [P, 5] int32
    nv,          # int32 scalar
    n_pat,       # int32 scalar
    mode,        # int32 scalar
    *,
    block_e: int = 64,
    block_t: int = 128,
    interpret: bool | None = None,
    lane_pad: bool | None = None,
):
    if interpret is None:
        interpret = default_interpret()
    if lane_pad is None:
        lane_pad = not interpret  # pad only when compiling for real
    E, T, _ = tok_e.shape
    NI, NV = phi.shape[1], psi.shape[1]
    if lane_pad:
        # PAD_PHI / PAD_PSI columns are inert: PAD_PHI is never equal to
        # or below a data itemset index, PAD_PSI never equals a data
        # vertex (>= NO_VERTEX = -1), so padded lookups cannot match
        NIp, NVp = _lane_pad_to(NI), _lane_pad_to(NV)
        if NIp != NI:
            phi = jnp.pad(phi, ((0, 0), (0, NIp - NI)),
                          constant_values=PAD_PHI)
            NI = NIp
        if NVp != NV:
            psi = jnp.pad(psi, ((0, 0), (0, NVp - NV)),
                          constant_values=PAD_PSI)
            NV = NVp
    Ep = -(-E // block_e) * block_e
    Tp = -(-T // block_t) * block_t
    if Ep != E or Tp != T:
        # zero padding gives tok valid=0 / emb_valid=0 -> INVALID_SIG
        tok_e = jnp.pad(tok_e, ((0, Ep - E), (0, Tp - T), (0, 0)))
        phi = jnp.pad(phi, ((0, Ep - E), (0, 0)))
        psi = jnp.pad(psi, ((0, Ep - E), (0, 0)))
        emb_valid = jnp.pad(emb_valid, (0, Ep - E))
    scal = jnp.stack([nv, n_pat, mode, jnp.int32(0)]).reshape(1, 4)
    grid = (Ep // block_e, Tp // block_t)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((6, block_e, block_t), lambda i, j: (0, i, j)),
            pl.BlockSpec((block_e, NI), lambda i, j: (i, 0)),
            pl.BlockSpec((block_e, NV), lambda i, j: (i, 0)),
            pl.BlockSpec((block_e, 1), lambda i, j: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_e, block_t), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Ep, Tp), jnp.int32),
        interpret=interpret,
    )(
        scal.astype(jnp.int32),
        jnp.moveaxis(tok_e.astype(jnp.int32), -1, 0),  # field-major
        phi.astype(jnp.int32),
        psi.astype(jnp.int32),
        emb_valid.astype(jnp.int32).reshape(-1, 1),
        existing.astype(jnp.int32),
    )
    return out[:E, :T]
