"""Pallas TPU kernel for the batched containment step (the serving hot
loop).

One query step evaluates the embedding-join predicate for every
(cell g, frontier row e, window token t) triple, where a *cell* is one
(sequence, pattern) pair of the serving batch - the flattened
sequences x patterns grid (dense, or prescreen-compacted to the
surviving pairs, see repro.serving.batch).  Per cell the step touches
its [Tm, 6] token window, its [E, NV] psi frontier and its [E, 8] step
table; E (frontier capacity) and Tm (token-window width) are small
statics, so the kernel grids over cells only and keeps whole cells in
VMEM - the [bG, E, Tm, NV] injectivity broadcasts live in VMEM/VREGs
instead of HBM.

Tiling: grid (G/bG,); per grid step the kernel touches
  tok block   [bG, 6, Tm]  int32 (field-major: tokens on lanes)
  psi/srow    [bG, E, NV], [bG, E, 8]
  out         [bG, E, Tm]  int32
Default bG=64 with E,Tm <= 32 keeps the working set well under 1 MB of
VMEM.

E and Tm are small statics well below the TPU tile (8 sublanes x 128
lanes), so every block load/store would be relayout-padded by the
hardware anyway; ``lane_pad`` makes the padding explicit up front - Tm
(the lane dim of the output / token axis) to the 128-lane boundary, E
(its sublane dim) to a multiple of 8 - with all-zero rows/tokens, which
are inert by the same argument as the bG padding (token valid=0 /
row_valid=0 -> no match bits).  It follows the existing backend
auto-select: on exactly when the kernel compiles for real
(interpret=False, i.e. on TPU), off in interpret mode where it only
adds work - interpret-mode parity is tested by forcing it on.

The kernel body ``contain_step_fields`` is ``ref.contain_step_core`` in
a form Mosaic lowers: the token window comes *field-major* (``[G, 6,
T]``: tokens on lanes, one sublane row per field) so every field is a
``[G, 1, T]`` slice and every step-table column a ``[G, E, 1]`` slice;
all work is on ``[G, E, T]`` or ``[G, E, NV]`` arrays, the psi-image
test is a static loop over the NV columns instead of a 4-D broadcast,
and selects between booleans are plain logic (``bool_where``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .. import default_interpret
from .ref import _BIG, SROW_FIELDS

LANE = 128
SUBLANE = 8


def bool_where(c, a, b):
    """``jnp.where`` over bool operands as pure logic: Mosaic cannot
    lower a select whose values are i1 vectors."""
    return (c & a) | (~c & b)


def srow_columns(srow):
    """[G,E,SROW_FIELDS] -> the tuple of its [G,E,1] columns."""
    return tuple(srow[:, :, c:c + 1] for c in range(SROW_FIELDS))


def contain_step_fields(tok_f, psi, cols):
    """``contain_step_core`` over a field-major token window
    ``tok_f [G,6,T]`` and the step table as its ``[G,E,1]`` columns
    ``cols`` - the Pallas kernel body."""
    t_ty = tok_f[:, 0:1, :]   # [G,1,T]
    u1 = tok_f[:, 1:2, :]
    u2 = tok_f[:, 2:3, :]
    t_lab = tok_f[:, 3:4, :]
    j = tok_f[:, 4:5, :]
    t_val = tok_f[:, 5:6, :] > 0

    sty, spu1, spu2, slab, snew, sprev, scur, sval = cols

    base = t_val & (sval > 0) & (t_ty == sty) & (t_lab == slab)
    slot_ok = bool_where(snew > 0, j > sprev, j == scur)

    # per-row psi gather at the step's pattern vertices (masked-min: the
    # matching column is unique, so the minimum is the looked-up value)
    NV = psi.shape[-1]
    nv_ids = lax.broadcasted_iota(jnp.int32, (1, 1, NV), 2)
    pvv1 = jnp.min(jnp.where(nv_ids == spu1, psi, _BIG), -1, keepdims=True)
    pvv2 = jnp.min(jnp.where(nv_ids == spu2, psi, _BIG), -1, keepdims=True)
    bound1 = (pvv1 >= 0) & (pvv1 < _BIG)
    bound2 = (pvv2 >= 0) & (pvv2 < _BIG)

    # injectivity: is a data vertex already in the psi image?  [G,E,T]
    u1_mapped = u2_mapped = jnp.zeros(u1.shape, jnp.bool_)
    for v in range(NV):
        col = psi[:, :, v:v + 1]  # [G,E,1]
        u1_mapped = u1_mapped | (col == u1)
        u2_mapped = u2_mapped | (col == u2)

    is_v = sty <= 2

    # edge orientations: v0 assigns (pu1->u1, pu2->u2), v1 the swap;
    # a vertex TR is e1_0 alone
    e1_0 = bool_where(bound1, u1 == pvv1, ~u1_mapped)
    e2_0 = bool_where(bound2, u2 == pvv2, ~u2_mapped)
    e1_1 = bool_where(bound1, u2 == pvv1, ~u2_mapped)
    e2_1 = bool_where(bound2, u1 == pvv2, ~u1_mapped)
    distinct = bound1 | bound2 | (u1 != u2)
    ok_e0 = e1_0 & e2_0 & distinct
    ok_e1 = e1_1 & e2_1 & distinct

    keep = base & slot_ok
    bit0 = keep & bool_where(is_v, e1_0, ok_e0)
    bit1 = keep & ~is_v & ok_e1
    return bit0.astype(jnp.int32) | (bit1.astype(jnp.int32) << 1)


def _kernel(tok_ref, psi_ref, srow_ref, out_ref):
    out_ref[...] = contain_step_fields(
        tok_ref[...], psi_ref[...], srow_columns(srow_ref[...])
    )


def contain_step_blocked(
    tok,        # [G, Tm, 6] int32 (per-cell token window)
    psi,        # [G, E, NV] int32
    srow,       # [G, E, 8] int32
    *,
    block_g: int = 64,
    interpret: bool | None = None,
    lane_pad: bool | None = None,
):
    if interpret is None:
        interpret = default_interpret()
    if lane_pad is None:
        lane_pad = not interpret  # pad only when compiling for real
    G, Tm, _ = tok.shape
    _, E, NV = psi.shape
    if lane_pad:
        Tp = -(-Tm // LANE) * LANE
        Ep = -(-E // SUBLANE) * SUBLANE
        if Tp != Tm:  # zero tokens: valid=0 -> no match bits
            tok = jnp.pad(tok, ((0, 0), (0, Tp - Tm), (0, 0)))
        if Ep != E:  # zero rows: row_valid=0 -> no match bits
            psi = jnp.pad(psi, ((0, 0), (0, Ep - E), (0, 0)))
            srow = jnp.pad(srow, ((0, 0), (0, Ep - E), (0, 0)))
        if Tp != Tm or Ep != E:
            out = contain_step_blocked(
                tok, psi, srow, block_g=block_g, interpret=interpret,
                lane_pad=False,
            )
            return out[:, :E, :Tm]
    Gp = -(-G // block_g) * block_g
    if Gp != G:
        # zero padding gives token valid=0 / row_valid=0 -> no match bits
        tok = jnp.pad(tok, ((0, Gp - G), (0, 0), (0, 0)))
        psi = jnp.pad(psi, ((0, Gp - G), (0, 0), (0, 0)))
        srow = jnp.pad(srow, ((0, Gp - G), (0, 0), (0, 0)))
    grid = (Gp // block_g,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_g, 6, Tm), lambda g: (g, 0, 0)),
            pl.BlockSpec((block_g, E, NV), lambda g: (g, 0, 0)),
            pl.BlockSpec((block_g, E, 8), lambda g: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_g, E, Tm), lambda g: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Gp, E, Tm), jnp.int32),
        interpret=interpret,
    )(
        jnp.swapaxes(tok.astype(jnp.int32), 1, 2),  # field-major window
        psi.astype(jnp.int32),
        srow.astype(jnp.int32),
    )
    return out[:G]
