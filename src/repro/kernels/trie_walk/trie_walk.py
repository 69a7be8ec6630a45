"""Pallas megakernel for the fused trie walk.

One grid step walks ``block_n`` (sequence, depth-1 subtree) cells
through their *entire* subtree - level iteration, frontier buffers and
the per-node residual prescreen all live inside the kernel body
(``walk_slots``), so a query batch costs one dispatch per subtree shard
regardless of trie depth.  The result is bit-identical to the jnp
reference ``ref.trie_walk_core`` (module docstring there has the
contract).  Per grid step the kernel touches

  windows       [bN, S, 6, W]  int32 (field-major, tokens on lanes)
  counts        [bN, S, 1], [bN, 1, K]
  steps/parent  [bN, S, 8], [bN, S, 1]
  req           [bN, S, K]
  out           2 x [bN, 1, S] int32 (accept / terminal-overflow bits)

with S = padded subtree slots and per-slot [bN, E, *] frontier state in
VMEM/VREGs; the default ``block_n=8`` keeps the working set small -
fused cells are ~S times heavier than a single containment step, so the
cell block is correspondingly narrower than containment's ``block_g``.

The walk is split where the TPU needs it.  ``slot_windows`` does the
token-table gathers - every slot's (type, label) window is fixed by its
step row, not by the frontier - and runs in XLA in front of the kernel;
``walk_slots`` is the kernel body.  It holds every value as a
``[cells, rows, lanes]`` array with the cell axis leading and reads the
small static axes (frontier rows, window tokens, NI/NV columns, parent
slots) by one-hot compare-and-reduce or select chains, never by gather
or scatter, which Mosaic does not lower.  Candidates are numbered
``2 * (row * W + token) + orientation``: for any window width
``W >= tmax`` that is the per-level path's (row, token, orientation)
order, so lane-padding the window changes no extraction.

``lane_pad`` follows the backend auto-select of the containment kernel
(repro.kernels.containment): on when compiling for real
(interpret=False, i.e. on TPU), off in interpret mode.  It widens the
window axis W from ``tmax`` to the 128-lane boundary with invalid
tokens; the candidate numbering keeps its order for any ``W >= tmax``,
so the result is unchanged.  Interpret-mode parity with forced
``lane_pad=True`` is covered by tests/test_trie_fused.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .. import default_interpret
from ..containment.containment import bool_where, contain_step_fields
from .ref import PAD_PHI, PAD_PSI, REQ_MASKED

LANE = 128


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _pick(table, ids, i):
    """``table[..., i]`` as a one-hot lane reduction: ``table [N,R,X]``,
    ``ids`` the ``[1,1,X]`` lane iota, ``i`` broadcastable to
    ``[N,R,1]``.  Exact whenever ``0 <= i < X``."""
    return jnp.sum(jnp.where(ids == i, table, 0), -1, keepdims=True)


def _rows(x, r):
    """[N,1,1] -> [N,r,1] through a select rather than a broadcast:
    Mosaic cannot broadcast a sliced [N,1,1] value along sublanes and
    lanes in one op, and it folds a chain of broadcasts into one."""
    return jnp.where(_iota((1, r, 1), 1) < r, x, 0)


def _any_row(x):
    """[N,R,1] bool -> [N,1,1]: any over the row (sublane) axis."""
    return jnp.max(x.astype(jnp.int32), 1, keepdims=True) > 0


def slot_windows(tok_c, order_c, start_c, count_c, steps, *, tmax, width):
    """Every (cell, slot) token window the walk will read - gathers, so
    they run in XLA in front of the kernel.  Returns
    ``(tok_w [N,S,6,width], ct [N,S,1])``: ``tok_w[i, s]`` is the
    field-major window of slot s's (type, label) bucket in cell i's
    sequence (tokens past ``tmax`` or past the bucket carry valid=0;
    ``width >= tmax`` only pads the lane axis), ``ct`` the bucket's full
    count, whose excess over ``tmax`` is the window overflow."""
    N, T, _ = tok_c.shape
    S = steps.shape[1]
    key = steps[..., 7].astype(jnp.int32)
    st = jnp.take_along_axis(start_c, key, axis=1)      # [N,S]
    ct = jnp.take_along_axis(count_c, key, axis=1)
    m_ids = jnp.arange(tmax, dtype=jnp.int32)
    wpos = jnp.minimum(st[..., None] + m_ids, T - 1)   # [N,S,tmax]
    wvalid = m_ids < ct[..., None]
    tpos = jnp.take_along_axis(order_c, wpos.reshape(N, S * tmax), axis=1)
    tok_w = jnp.take_along_axis(tok_c, tpos[..., None], axis=1)
    tok_w = tok_w.reshape(N, S, tmax, 6)
    tok_w = tok_w.at[..., 5].set(jnp.where(wvalid, tok_w[..., 5], 0))
    tok_w = jnp.moveaxis(tok_w, -1, 2)                 # [N,S,6,tmax]
    if width > tmax:  # zero tokens: valid=0
        tok_w = jnp.pad(tok_w, ((0, 0),) * 3 + ((0, width - tmax),))
    return tok_w, ct[..., None]


def _walk_step(tok_f, ct, step, phi, psi, valid, *, emax, tmax):
    """One embedding-join step for N cells - the in-kernel form of
    ``serving.batch._step_once`` (``uniform=False``).  ``tok_f [N,6,W]``
    is the slot's window, ``ct [N,1,1]`` its bucket count, ``step
    [N,1,8]`` its step row, ``phi [N,E,NI]`` / ``psi [N,E,NV]`` /
    ``valid [N,E,1]`` the seed frontier.  Returns ``(phi_new, psi_new,
    new_valid, frontier_ovf, window_ovf)`` - both overflow legs
    separately, so the caller can assemble the per-level path's
    ``ovf_state`` (children inherit) vs ``ovf_term`` (terminal
    undecidedness drops this step's own frontier overflow)."""
    N, Ein, NI = phi.shape
    NV = psi.shape[2]
    W = tok_f.shape[-1]
    E = emax
    C = Ein * W * 2  # candidates: frontier rows x window x orient
    ni_ids = _iota((1, 1, NI), 2)
    nv_ids = _iota((1, 1, NV), 2)
    m_ids = _iota((1, 1, W), 2)
    ty_s, pu1_s, pu2_s, lab_s, new_s, idx_s, sval_s = (
        step[:, :, c:c + 1] for c in range(7)      # [N,1,1] each
    )

    # ---- per-row step table for the predicate
    cur_phi = _pick(phi, ni_ids, idx_s)            # [N,E,1]
    prev_phi = _pick(phi, ni_ids, jnp.clip(idx_s - 1, 0, NI - 1))
    prev_phi = jnp.where(idx_s > 0, prev_phi, -1)
    row_valid = valid & (sval_s > 0)

    bits = contain_step_fields(tok_f, psi, tuple(
        _rows(x, Ein) for x in (ty_s, pu1_s, pu2_s, lab_s, new_s)
    ) + (prev_phi, cur_phi, row_valid.astype(jnp.int32)))  # [N,E,W]

    # ---- first-emax compaction by iterative min-extraction over the
    # candidates laid out along lanes (row-major, as _step_once orders
    # them), so the kept slots and their order agree bitwise
    flag0 = jnp.concatenate(
        [(bits[:, e:e + 1, :] & 1) > 0 for e in range(Ein)], -1)
    flag1 = jnp.concatenate(
        [(bits[:, e:e + 1, :] & 2) > 0 for e in range(Ein)], -1)
    id0 = 2 * _iota((1, 1, Ein * W), 2)            # orientation 0
    id1 = id0 + 1

    def next_cand(lo):  # smallest flagged candidate id >= lo
        c0 = jnp.min(jnp.where(flag0 & (id0 >= lo), id0, C), -1,
                     keepdims=True)
        c1 = jnp.min(jnp.where(flag1 & (id1 >= lo), id1, C), -1,
                     keepdims=True)
        return jnp.minimum(c0, c1)                 # [N,1,1]

    window_ovf = (ct > tmax) & _any_row(valid)
    e_ids = _iota((1, E, 1), 1)
    sel = jnp.full((N, E, 1), C, jnp.int32)
    cur = next_cand(0)
    for i in range(E):
        sel = jnp.where(e_ids == i, cur, sel)
        cur = next_cand(cur + 1)
    frontier_ovf = cur < C
    new_valid = sel < C                            # [N,E,1]
    sel = jnp.minimum(sel, C - 1)
    # decode (row, token, orientation) without vector division
    k = sel >> 1
    e_old = jnp.zeros_like(k)
    for e in range(1, Ein):
        e_old = e_old + (k >= e * W).astype(jnp.int32)
    t_w = k - e_old * W
    var = sel & 1

    phi_src = jnp.zeros((N, E, NI), jnp.int32)
    psi_src = jnp.zeros((N, E, NV), jnp.int32)
    for e in range(Ein):
        hit = e_old == e
        phi_src = jnp.where(hit, phi[:, e:e + 1, :], phi_src)
        psi_src = jnp.where(hit, psi[:, e:e + 1, :], psi_src)

    def wfield(f):  # [N,E,1]: tok_f[n, f, t_w[n, e]]
        return _pick(tok_f[:, f:f + 1, :], m_ids, t_w)

    u1_g, u2_g, j_g = wfield(1), wfield(2), wfield(4)

    claim = (new_s > 0) & new_valid
    onehot_ni = ni_ids == idx_s                    # [N,1,NI]
    phi_new = jnp.where(onehot_ni & claim, j_g, phi_src)

    a_g = jnp.where(var == 0, u1_g, u2_g)
    b_g = jnp.where(var == 0, u2_g, u1_g)
    is_v = ty_s <= 2
    fresh1 = _pick(psi_src, nv_ids, pu1_s) < 0
    fresh2 = _pick(psi_src, nv_ids, pu2_s) < 0
    onehot1 = nv_ids == pu1_s
    onehot2 = nv_ids == pu2_s
    assign1 = jnp.where(is_v, u1_g, a_g)
    psi_new = jnp.where(onehot1 & (fresh1 & new_valid), assign1, psi_src)
    psi_new = jnp.where(
        onehot2 & ((~is_v) & fresh2 & new_valid), b_g, psi_new)
    return phi_new, psi_new, new_valid, frontier_ovf, window_ovf


def walk_slots(tok_w, ct, count, steps, parent, req, *, emax, tmax, ni,
               nv):
    """The fused walk over prepared windows - the Pallas kernel body.
    ``tok_w [N,S,6,W]`` / ``ct [N,S,1]`` come from ``slot_windows``,
    ``count [N,1,K]`` is each cell's bucket-count row, ``steps
    [N,S,8]`` / ``parent [N,S,1]`` / ``req [N,S,K]`` its packed
    subtree, all int32.  Inputs are read one slot at a time with static
    slices, so they may be arrays or the kernel's refs (a ref slice is
    a load at a static offset).  Returns ``(acc, ovf_term)`` as
    ``[N,1,S]`` int32 (0/1)."""
    N, S, _ = steps.shape
    E = emax
    count = count[...]
    # the per-level root seed (trie_root_state) widened to E rows with
    # only row 0 valid - bitwise the same compacted outputs (ref module
    # docstring)
    root = (
        jnp.full((N, E, ni), PAD_PHI, jnp.int32),
        jnp.full((N, E, nv), PAD_PSI, jnp.int32),
        jnp.broadcast_to(_iota((1, E, 1), 1) == 0, (N, E, 1)),
        jnp.zeros((N, 1, 1), jnp.bool_),
    )
    s_ids = _iota((1, 1, S), 2)
    acc = jnp.zeros((N, 1, S), jnp.int32)
    ovft = jnp.zeros((N, 1, S), jnp.int32)
    done = []  # per finished slot: (phi, psi, valid, ovf_state)
    for n in range(S):
        # seed from the parent slot's frontier (parents precede their
        # children), or the root state when parent < 0
        pidx = parent[:, n:n + 1, :]               # [N,1,1]
        pidx_rows = _rows(pidx, E)                 # [N,E,1]
        seed_phi, seed_psi, seed_valid, seed_ovf = root
        for m, (phi_m, psi_m, valid_m, ovf_m) in enumerate(done):
            hit = pidx_rows == m
            seed_phi = jnp.where(hit, phi_m, seed_phi)
            seed_psi = jnp.where(hit, psi_m, seed_psi)
            seed_valid = bool_where(hit, valid_m, seed_valid)
            seed_ovf = bool_where(pidx == m, ovf_m, seed_ovf)
        # in-kernel per-node residual prescreen.  A failing node's
        # frontier dies before the step: no candidates, no window
        # overflow - exactly the per-level scan never seeding the cell
        # (req monotonicity makes the whole subtree agree)
        poss = jnp.min((count >= req[:, n:n + 1, :]).astype(jnp.int32),
                       -1, keepdims=True) > 0      # [N,1,1]
        seed_valid = seed_valid & poss
        phi_n, psi_n, new_valid, frontier_ovf, window_ovf = _walk_step(
            tok_w[:, n], ct[:, n:n + 1, :], steps[:, n:n + 1, :],
            seed_phi, seed_psi, seed_valid, emax=emax, tmax=tmax,
        )
        at_n = s_ids == n
        acc = jnp.where(
            at_n, (_any_row(new_valid) & poss).astype(jnp.int32), acc)
        ovft = jnp.where(
            at_n, ((seed_ovf | window_ovf) & poss).astype(jnp.int32), ovft)
        done.append((phi_n, psi_n, new_valid,
                     (seed_ovf | frontier_ovf | window_ovf) & poss))
    return acc, ovft


def _make_kernel(emax, tmax, ni, nv):
    def _kernel(tok_ref, ct_ref, count_ref, steps_ref, parent_ref,
                req_ref, acc_ref, ovft_ref):
        acc_ref[...], ovft_ref[...] = walk_slots(
            tok_ref, ct_ref, count_ref, steps_ref, parent_ref, req_ref,
            emax=emax, tmax=tmax, ni=ni, nv=nv,
        )

    return _kernel


def trie_walk_blocked(
    tok_c,      # [N, T, 6] int32 (per-cell token tables)
    order_c,    # [N, T] int32 (per-cell inverted index)
    start_c,    # [N, K] int32
    count_c,    # [N, K] int32
    steps,      # [N, S, 8] int32 (packed subtree per cell)
    parent,     # [N, S] int32 (slot of parent; -1 = root seed / pad)
    req,        # [N, S, K] int32 (per-node residual prescreen rows)
    *,
    emax: int,
    tmax: int,
    ni: int,
    nv: int,
    block_n: int = 8,
    interpret: bool | None = None,
    lane_pad: bool | None = None,
):
    """Returns ``(acc [N,S] int32, ovf_term [N,S] int32)`` - the fused
    walk's terminal accept / undecidedness bits per subtree slot (see
    ref.trie_walk_core for the exact per-level bit-identity contract).
    """
    if interpret is None:
        interpret = default_interpret()
    if lane_pad is None:
        lane_pad = not interpret  # pad only when compiling for real
    N = tok_c.shape[0]
    K = start_c.shape[1]
    S = steps.shape[1]
    width = -(-tmax // LANE) * LANE if lane_pad else tmax
    steps = steps.astype(jnp.int32)
    tok_w, ct = slot_windows(
        tok_c.astype(jnp.int32), order_c.astype(jnp.int32),
        start_c.astype(jnp.int32), count_c.astype(jnp.int32), steps,
        tmax=tmax, width=width,
    )
    count = count_c.astype(jnp.int32)[:, None, :]
    parent = parent.astype(jnp.int32)[..., None]
    req = req.astype(jnp.int32)
    Np = -(-N // block_n) * block_n
    if Np != N:
        # zero cells: empty windows + REQ_MASKED prescreen rows accept
        # nothing; the real rows are sliced back below
        def pad(x, value=0):
            widths = ((0, Np - N),) + ((0, 0),) * (x.ndim - 1)
            return jnp.pad(x, widths, constant_values=value)

        tok_w, ct, count, steps = pad(tok_w), pad(ct), pad(count), \
            pad(steps)
        parent = pad(parent, -1)
        req = pad(req, REQ_MASKED)

    def block(*shape):
        return pl.BlockSpec(
            (block_n,) + shape, lambda g: (g,) + (0,) * len(shape))

    acc, ovft = pl.pallas_call(
        _make_kernel(emax, tmax, ni, nv),
        grid=(Np // block_n,),
        in_specs=[
            block(S, 6, width),
            block(S, 1),
            block(1, K),
            block(S, 8),
            block(S, 1),
            block(S, K),
        ],
        out_specs=[block(1, S), block(1, S)],
        out_shape=[
            jax.ShapeDtypeStruct((Np, 1, S), jnp.int32),
            jax.ShapeDtypeStruct((Np, 1, S), jnp.int32),
        ],
        interpret=interpret,
    )(tok_w, ct, count, steps, parent, req)
    return acc[:N, 0], ovft[:N, 0]
