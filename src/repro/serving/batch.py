"""Batched on-device containment: TRSeq batch x pattern bank -> bool.

The Def-4 containment test is replayed as an *embedding join*: per
(sequence, pattern) cell we scan the pattern's step program (bank.py)
and maintain a fixed-capacity frontier of partial embeddings (phi over
claimed data itemsets, psi over bound data vertices).  One step
evaluates the match predicate for every
(frontier row x window token x orientation) candidate - the containment
kernel or its jnp oracle - then compacts the accepted candidates back
into the ``emax`` frontier slots.  The pattern is contained iff its
frontier is non-empty after its last step.

Three query-time reductions keep the join off the B*P*T dense wall:

* **inverted token index** - tokens are bucketed per sequence by
  (type, label) key; a step only ever scans its own bucket, a ``tmax``
  window instead of all T tokens,
* **counts prescreen** (``prescreen_counts``) - psi injectivity +
  strictly increasing phi force distinct pattern TRs onto distinct data
  tokens, so ``counts[b] >= bank.req[p]`` (per key) is a sound
  necessary condition; the server joins only surviving pairs
  (``pair_contains``), typically a small fraction.  The streaming
  layer's tombstone mask rides on this: a masked pattern's ``req`` row
  (or a dead trie subtree's ``node_req``) is set to ``trie.REQ_MASKED``,
  which no count vector satisfies, so tombstoned rows are pruned here
  at zero join cost,
* **sort compaction** - frontier selection is "first emax accepted
  candidates", computed with one small sort per cell (top_k is an order
  of magnitude slower on CPU backends).

Exactness: every kept embedding is a genuine prefix embedding, so
``contained=True`` is always exact - truncation (frontier or token
window) can only lose matches, and any step that may have lost one sets
the cell's ``overflow`` flag.  Only ``overflow & ~contained`` cells are
undecided; the server re-checks just those against the host oracle.

The whole scan is one jitted program (the step loop unrolls - L is
small), so a serving step costs L kernel launches regardless of bank
size, and shapes are static per (batch bucket, bank) pair.

**Trie layout** (trie.py): the same step dynamics, but one frontier per
(sequence, trie *node*) instead of per (sequence, pattern) - a
level-synchronous scan over trie depth where a node's frontier is
seeded from its parent's compacted frontier, so patterns sharing a
program prefix share its join work.  Entry points: dense
``trie_contains`` (shard_map-able via ``trie_contains_ref``), and the
server's per-level ``trie_root_advance`` /
``trie_level_advance_gather`` (seed gather fused into the jitted
program - one dispatch per level) with the per-node residual-``req``
prescreen ``index_and_node_prescreen``.  Because ``_step_once`` is
shared and deterministic, trie and flat joins are bit-identical in both
``contained`` and ``overflow``; the soundness contract above carries
over unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.containment.containment import contain_step_blocked
from ..kernels.containment.ref import contain_step_core
from ..kernels.trie_walk import ref as _fused_ref
from ..kernels.trie_walk import trie_walk_blocked, trie_walk_core
from ..mining.encoding import PAD_PHI, PAD_PSI
from .trie import REQ_MASKED

# the kernels layer mirrors the serving constants locally (it stays
# import-free of repro.serving); pin the mirrors here so a drift breaks
# loudly at import instead of silently de-synchronizing the fused walk
assert _fused_ref.PAD_PHI == int(PAD_PHI)
assert _fused_ref.PAD_PSI == int(PAD_PSI)
assert _fused_ref.REQ_MASKED == REQ_MASKED


def token_keys_np(tokens: np.ndarray, n_label_keys: int) -> np.ndarray:
    """Host mirror of the device key computation ([B,T] int, 6*NL =
    out-of-bank dump key)."""
    NL = n_label_keys
    ty, lab, val = tokens[..., 0], tokens[..., 3], tokens[..., 5]
    lab1 = lab + 1
    ok = (val > 0) & (lab1 >= 0) & (lab1 < NL)
    return np.where(ok, ty * NL + lab1, 6 * NL)


def max_key_bucket(tokens: np.ndarray, n_label_keys: int) -> int:
    """Largest same-key token bucket in the batch: the exact ``tmax``
    (no window overflow).  Host-side helper for callers of the jitted
    entry points."""
    key = token_keys_np(np.asarray(tokens), n_label_keys)
    K = 6 * n_label_keys
    B = key.shape[0]
    rowed = (key + np.arange(B)[:, None] * (K + 1)).ravel()
    rowed = rowed[(key < K).ravel()]
    if not rowed.size:
        return 1
    return max(int(np.bincount(rowed).max()), 1)


def build_token_index(tokens, *, n_label_keys: int):
    """[B,T,6] -> (order [B,T], start [B,K], count [B,K]); bucket k of
    sequence b is order[b, start[b,k] : start[b,k]+count[b,k]].  Tokens
    whose label falls outside the bank's label space go to a dump bucket
    - they can never match a bank step."""
    NL = n_label_keys
    K = 6 * NL
    B, T, _ = tokens.shape
    ty = tokens[..., 0]
    lab1 = tokens[..., 3] + 1
    ok = (tokens[..., 5] > 0) & (lab1 >= 0) & (lab1 < NL)
    key = jnp.where(ok, ty * NL + lab1, K).astype(jnp.int32)
    # composite sort key makes the order unique hence fully deterministic
    t_ids = jnp.arange(T, dtype=jnp.int32)
    order = jnp.argsort(key * T + t_ids[None, :], axis=1)
    kcol = jnp.arange(K, dtype=jnp.int32)
    count = (key[:, :, None] == kcol[None, None, :]).sum(1)
    start = jnp.cumsum(count, -1) - count
    return order.astype(jnp.int32), start.astype(jnp.int32), \
        count.astype(jnp.int32)


# jitted alias of build_token_index: the index depends on the query
# batch alone (never on the bank), so the cluster router builds it once
# per flush and ships it to every shard (server.encode_queries)
token_index = jax.jit(
    build_token_index, static_argnames=("n_label_keys",)
)


@functools.partial(jax.jit, static_argnames=("n_label_keys",))
def prescreen_counts(tokens, req, *, n_label_keys: int):
    """Sound necessary condition: possible[b,p] = counts_b >= req_p
    elementwise over token keys (see bank.req)."""
    _, _, count = build_token_index(tokens, n_label_keys=n_label_keys)
    return (count[:, None, :] >= req[None, :, :]).all(-1)


@functools.partial(jax.jit, static_argnames=("n_label_keys",))
def index_and_prescreen(tokens, req, *, n_label_keys: int):
    """One pass producing both the inverted token index and the
    prescreen matrix, so a serving batch builds the index once and
    shares it across the per-group ``pair_contains_indexed`` calls."""
    order, start, count = build_token_index(
        tokens, n_label_keys=n_label_keys
    )
    possible = (count[:, None, :] >= req[None, :, :]).all(-1)
    return order, start, count, possible


def _step_once(tokens, order, start, count, cell_b, step_k, phi, psi,
               valid, *, emax, tmax, use_kernel, block_g, uniform,
               compact, count_frontier_ovf=False):
    """One embedding-join step for N cells: evaluate the match predicate
    for every (frontier row x window token x orientation) candidate of
    step row ``step_k[i]`` against sequence ``cell_b[i]``, then compact
    the accepted candidates into ``emax`` frontier slots.

    This is the shared core of both bank layouts: the flat per-pattern
    scan (``_join``) replays each pattern's whole program through it,
    the trie join advances one frontier per (sequence, trie node) and
    calls it once per trie level.  ``uniform`` promises every step row
    is real (no ``step_valid=0`` padding), dropping one select.

    Returns ``(phi_new, psi_new, new_valid, step_ovf)`` with
    ``step_ovf = frontier_ovf | window_ovf``; with ``compact=False``
    (terminal steps, where only "any candidate accepted" is needed)
    skips compaction entirely and returns ``(accepted, step_ovf)``.
    There ``count_frontier_ovf`` picks the overflow semantics: False
    omits frontier overflow (exact and cheaper - nothing follows a
    terminal step, so dropped candidates cannot lose anything; the
    uniform-length flat path and the server's trie leaves do this),
    True folds in ``#accepted > emax``, which equals the compacted
    path's frontier flag bit-for-bit (dense ``trie_contains`` uses it
    to stay bit-identical to dense ``batch_contains``, whose unpadded
    final steps do run compaction).
    """
    T = tokens.shape[1]
    N, Ein, NI = phi.shape  # Ein: 1 on the root frontier, E afterwards
    NV = psi.shape[2]
    E, Tm = emax, tmax
    C = Ein * Tm * 2  # candidates: frontier rows x window x orient
    nv_ids = jnp.arange(NV, dtype=jnp.int32)
    ni_ids = jnp.arange(NI, dtype=jnp.int32)
    m_ids = jnp.arange(Tm, dtype=jnp.int32)
    cand_ids = jnp.arange(C, dtype=jnp.int32)
    ty_s, pu1_s, pu2_s, lab_s, new_s, idx_s, sval_s, key_s = (
        step_k[:, c] for c in range(8)
    )

    # ---- per-cell token window for this step's (type,label) bucket
    st_sel = start[cell_b, key_s]   # [N]
    ct_sel = count[cell_b, key_s]
    wpos = jnp.minimum(st_sel[:, None] + m_ids[None, :], T - 1)
    wvalid = m_ids[None, :] < ct_sel[:, None]
    tpos = order[cell_b[:, None], wpos]       # [N, Tm]
    tok_w = tokens[cell_b[:, None], tpos]     # [N, Tm, 6]
    tok_w = tok_w.at[..., 5].set(
        jnp.where(wvalid, tok_w[..., 5], 0)
    )

    # ---- per-row step table for the predicate
    idx_b = jnp.broadcast_to(idx_s[:, None, None], (N, Ein, 1))
    cur_phi = jnp.take_along_axis(phi, idx_b, axis=-1)[..., 0]
    prev_b = jnp.clip(idx_b - 1, 0, NI - 1)
    prev_phi = jnp.take_along_axis(phi, prev_b, axis=-1)[..., 0]
    prev_phi = jnp.where(idx_s[:, None] > 0, prev_phi, -1)
    if uniform:
        row_valid = valid  # every step row is a real step
    else:
        row_valid = valid & (sval_s[:, None] > 0)

    def bro(x):  # [N] -> [N, Ein]
        return jnp.broadcast_to(x[:, None], (N, Ein))

    srow = jnp.stack(
        [bro(ty_s), bro(pu1_s), bro(pu2_s), bro(lab_s), bro(new_s),
         prev_phi, cur_phi, row_valid.astype(jnp.int32)],
        axis=-1,
    )

    # ---- match predicate over (cell, row, window token)
    if use_kernel:
        bits = contain_step_blocked(tok_w, psi, srow, block_g=block_g)
    else:
        bits = contain_step_core(tok_w, psi, srow)

    # ---- compact accepted candidates into the emax frontier slots:
    # first E in (row, token, orientation) order, by iterative
    # min-extraction - E passes of trivial ops beat a [N, C] sort by
    # a wide margin on CPU and keep everything in VREG-sized tiles
    flags = (
        jnp.stack([bits & 1, (bits >> 1) & 1], -1) > 0
    ).reshape(N, C)
    # a truncated window may lose matches only if the frontier was
    # still live going into the step
    window_ovf = (ct_sel > Tm) & valid.any(-1)
    if not compact:
        if count_frontier_ovf:
            # equals the compacted path's frontier flag: the first-E
            # extraction leaves a flagged candidate iff #accepted > E
            frontier_ovf = flags.sum(-1) > E
            return flags.any(-1), window_ovf | frontier_ovf
        return flags.any(-1), window_ovf
    cand_row = cand_ids[None, :]
    sels = []
    last = jnp.full((N, 1), -1, jnp.int32)
    for _ in range(E):
        cur = jnp.min(
            jnp.where(flags & (cand_row > last), cand_row, C),
            -1, keepdims=True,
        )
        sels.append(cur)
        last = cur
    # anything still flagged past the E extracted slots was dropped
    frontier_ovf = jnp.min(
        jnp.where(flags & (cand_row > last), cand_row, C), -1
    ) < C
    sel = jnp.concatenate(sels, -1)  # [N, E] ascending, C = empty
    new_valid = sel < C
    sel = jnp.minimum(sel, C - 1)
    e_old = sel // (Tm * 2)
    t_w = (sel // 2) % Tm
    var = sel % 2

    phi_src = jnp.take_along_axis(phi, e_old[..., None], axis=1)
    psi_src = jnp.take_along_axis(psi, e_old[..., None], axis=1)

    def wfield(f):  # [N, E] gather of tok_w[n, t_w, f]
        return jnp.take_along_axis(tok_w[..., f], t_w, axis=1)

    u1_g, u2_g, j_g = wfield(1), wfield(2), wfield(4)

    # phi: the first TR of a new pattern itemset claims data itemset j
    claim = (new_s[:, None] > 0) & new_valid
    onehot_ni = ni_ids[None, None, :] == idx_s[:, None, None]
    phi_new = jnp.where(
        onehot_ni & claim[..., None], j_g[..., None], phi_src
    )

    # psi: fresh pattern vertices bind per the matched orientation
    a_g = jnp.where(var == 0, u1_g, u2_g)
    b_g = jnp.where(var == 0, u2_g, u1_g)
    is_v = (ty_s <= 2)[:, None]
    pu1_b = jnp.broadcast_to(pu1_s[:, None, None], (N, E, 1))
    pu2_b = jnp.broadcast_to(pu2_s[:, None, None], (N, E, 1))
    fresh1 = jnp.take_along_axis(psi_src, pu1_b, axis=-1)[..., 0] < 0
    fresh2 = jnp.take_along_axis(psi_src, pu2_b, axis=-1)[..., 0] < 0
    onehot1 = nv_ids[None, None, :] == pu1_b
    onehot2 = nv_ids[None, None, :] == pu2_b
    assign1 = jnp.where(is_v, u1_g, a_g)
    psi_new = jnp.where(
        onehot1 & (fresh1 & new_valid)[..., None],
        assign1[..., None], psi_src,
    )
    psi_new = jnp.where(
        onehot2 & ((~is_v) & fresh2 & new_valid)[..., None],
        b_g[..., None], psi_new,
    )
    return phi_new, psi_new, new_valid, frontier_ovf | window_ovf


def _join(tokens, order, start, count, cell_b, cell_steps, *,
          nv, emax, tmax, use_kernel, block_g, uniform_length=False):
    """The embedding-join scan over N cells (cell i = sequence
    cell_b[i] vs step program cell_steps[i]).  ``uniform_length``
    promises every cell's program is exactly L steps (no padding rows),
    which drops the pass-through selects and lets the final step skip
    compaction and the state update entirely.  Returns
    (contained [N] bool, overflow [N] bool)."""
    N, L, _ = cell_steps.shape
    NI = L  # a pattern has at most as many itemsets as steps
    tokens = tokens.astype(jnp.int32)
    cell_steps = cell_steps.astype(jnp.int32)
    cell_b = cell_b.astype(jnp.int32)

    # step 0 always joins against the single root embedding, so the
    # initial frontier is one row; compaction widens it to E rows
    phi = jnp.full((N, 1, NI), PAD_PHI, jnp.int32)
    psi = jnp.full((N, 1, nv), PAD_PSI, jnp.int32)
    valid = jnp.ones((N, 1), jnp.bool_)
    overflow = jnp.zeros((N,), jnp.bool_)

    # NOTE: an unrolled python loop, not lax.scan.  It was chosen when
    # scan inside shard_map dropped matches on the jax 0.4 CPU backend;
    # tests/test_scan_shardmap.py shows the installed jax agrees, so
    # the loop stays unrolled only until a measurement on the chip
    # picks between the two.
    for k in range(L):
        step_k = cell_steps[:, k]
        if uniform_length and k == L - 1:
            # every cell ends at step L-1: containment just needs "any
            # candidate accepted", so compaction is skipped entirely
            accepted, window_ovf = _step_once(
                tokens, order, start, count, cell_b, step_k,
                phi, psi, valid, emax=emax, tmax=tmax,
                use_kernel=use_kernel, block_g=block_g,
                uniform=True, compact=False,
            )
            return accepted, overflow | window_ovf
        phi_new, psi_new, new_valid, ovf_step = _step_once(
            tokens, order, start, count, cell_b, step_k,
            phi, psi, valid, emax=emax, tmax=tmax,
            use_kernel=use_kernel, block_g=block_g,
            uniform=uniform_length, compact=True,
        )
        if uniform_length:
            phi, psi, valid = phi_new, psi_new, new_valid
            overflow = overflow | ovf_step
        else:
            # ---- pass-through for cells already past their last step
            alive = step_k[:, 6] > 0
            phi = jnp.where(alive[:, None, None], phi_new, phi)
            psi = jnp.where(alive[:, None, None], psi_new, psi)
            valid = jnp.where(alive[:, None], new_valid, valid)
            overflow = jnp.where(alive, ovf_step | overflow, overflow)
    return valid.any(-1), overflow


@functools.partial(
    jax.jit,
    static_argnames=(
        "nv", "n_label_keys", "emax", "tmax", "use_kernel", "block_g",
        "uniform_length",
    ),
)
def pair_contains(
    tokens,   # [B, T, 6] int32
    steps,    # [P, L, STEP_FIELDS] int32
    b_idx,    # [N] int32: sequence per cell
    p_idx,    # [N] int32: pattern row per cell
    *,
    nv: int,
    n_label_keys: int,
    emax: int = 8,
    tmax: int = 16,
    use_kernel: bool = False,
    block_g: int = 64,
    uniform_length: bool = False,
):
    """Containment over a compacted (sequence, pattern) pair list - the
    server's post-prescreen path.  Returns (contained [N], overflow [N])."""
    order, start, count = build_token_index(
        tokens, n_label_keys=n_label_keys
    )
    return _join(
        tokens, order, start, count, b_idx, steps[p_idx],
        nv=nv, emax=emax, tmax=tmax,
        use_kernel=use_kernel, block_g=block_g,
        uniform_length=uniform_length,
    )


@functools.partial(
    jax.jit,
    static_argnames=("nv", "emax", "tmax", "use_kernel", "block_g",
                     "uniform_length"),
)
def pair_contains_indexed(
    tokens, order, start, count,  # tokens + prebuilt inverted index
    steps, b_idx, p_idx,
    *,
    nv: int,
    emax: int = 8,
    tmax: int = 16,
    use_kernel: bool = False,
    block_g: int = 64,
    uniform_length: bool = False,
):
    """``pair_contains`` with the token index precomputed (see
    ``index_and_prescreen``)."""
    return _join(
        tokens, order, start, count, b_idx, steps[p_idx],
        nv=nv, emax=emax, tmax=tmax,
        use_kernel=use_kernel, block_g=block_g,
        uniform_length=uniform_length,
    )


# --------------------------------------------------------------- trie join
#
# The trie layout (trie.py) deduplicates shared prefix work: instead of
# one frontier per (sequence, pattern) replaying the whole program, the
# join advances one frontier per (sequence, trie node) in a
# level-synchronous scan over trie depth - a node's frontier is seeded
# from its parent's compacted frontier, so sibling patterns pay for
# their common prefix exactly once.  The per-step dynamics are the same
# ``_step_once`` as the flat join (same candidate order, same first-emax
# compaction, same overflow flags), so for every pattern the frontier
# sequence along its root-to-terminal path is *bit-identical* to the
# flat join's - contained AND overflow agree exactly, and the
# overflow-soundness contract carries over unchanged.


def trie_root_state(n: int, ni: int, nv: int):
    """The seed state for depth-1 trie cells: one root embedding per
    cell, exactly the flat join's step-0 frontier."""
    phi = jnp.full((n, 1, ni), PAD_PHI, jnp.int32)
    psi = jnp.full((n, 1, nv), PAD_PSI, jnp.int32)
    valid = jnp.ones((n, 1), jnp.bool_)
    ovf = jnp.zeros((n,), jnp.bool_)
    return phi, psi, valid, ovf


def trie_level_advance_ref(
    tokens, order, start, count,   # tokens + prebuilt inverted index
    seed_phi, seed_psi, seed_valid, seed_ovf,  # [N,Ein,*], [N,Ein], [N]
    cell_b, cell_step,             # [N], [N, STEP_FIELDS]
    *,
    emax: int,
    tmax: int,
    use_kernel: bool = False,
    block_g: int = 64,
    compact: bool = True,
    count_frontier_ovf: bool = False,
):
    """Advance N (sequence, trie node) cells one step from their seeded
    parent frontiers - the server's per-level entry point.  Returns
    ``(phi, psi, valid, accepted [N], ovf_state [N], ovf_term [N])``;
    with ``compact=False`` (leaf cells) just ``(accepted, ovf)``, where
    ``count_frontier_ovf`` selects the terminal-step overflow semantics
    (see ``_step_once``).  ``ovf_state`` (path frontier + window
    losses) is what children must inherit; ``ovf_term`` drops this
    step's own frontier overflow - the accept bit is exact no matter
    what compaction dropped, so a terminal ending *here* is undecided
    only via ``ovf_term`` (exactly the flat uniform-length semantics;
    using ``ovf_state`` for terminals would spuriously escalate).
    Padding cells carry ``step_valid=0`` rows, ``accepted=False``."""
    tokens = tokens.astype(jnp.int32)
    cell_step = cell_step.astype(jnp.int32)
    cell_b = cell_b.astype(jnp.int32)
    if not compact:
        accepted, step_ovf = _step_once(
            tokens, order, start, count, cell_b, cell_step,
            seed_phi, seed_psi, seed_valid, emax=emax, tmax=tmax,
            use_kernel=use_kernel, block_g=block_g,
            uniform=False, compact=False,
            count_frontier_ovf=count_frontier_ovf,
        )
        return accepted, seed_ovf | step_ovf
    phi, psi, valid, ovf_step = _step_once(
        tokens, order, start, count, cell_b, cell_step,
        seed_phi, seed_psi, seed_valid, emax=emax, tmax=tmax,
        use_kernel=use_kernel, block_g=block_g,
        uniform=False, compact=True,
    )
    ct_sel = count[cell_b, cell_step[:, 7]]
    window_ovf = (ct_sel > tmax) & seed_valid.any(-1)
    return (phi, psi, valid, valid.any(-1), seed_ovf | ovf_step,
            seed_ovf | window_ovf)


trie_level_advance = functools.partial(
    jax.jit,
    static_argnames=("emax", "tmax", "use_kernel", "block_g", "compact",
                     "count_frontier_ovf"),
)(trie_level_advance_ref)


@functools.partial(
    jax.jit,
    static_argnames=("ni", "nv", "emax", "tmax", "use_kernel", "block_g",
                     "compact"),
)
def trie_root_advance(
    tokens, order, start, count, cells,
    *,
    ni: int,
    nv: int,
    emax: int,
    tmax: int,
    use_kernel: bool = False,
    block_g: int = 64,
    compact: bool = True,
):
    """``trie_level_advance`` for depth-1 cells: the root seed (one
    root embedding per cell) is built inside the jitted program, so the
    whole level costs a single dispatch.  ``cells`` packs
    ``[cell_b, parent_idx(unused), step row]`` as one [N, 2+F] int32
    upload (the server's per-call host->device traffic)."""
    seed = trie_root_state(cells.shape[0], ni, nv)
    return trie_level_advance_ref(
        tokens, order, start, count, *seed, cells[:, 0], cells[:, 2:],
        emax=emax, tmax=tmax, use_kernel=use_kernel, block_g=block_g,
        compact=compact,
    )


@functools.partial(
    jax.jit,
    static_argnames=("emax", "tmax", "use_kernel", "block_g", "compact"),
)
def trie_level_advance_gather(
    tokens, order, start, count,
    prev_phi, prev_psi, prev_valid, prev_ovf,  # previous level's cells
    cells,  # [N, 2+F] int32: cell_b, parent cell index, step row
    *,
    emax: int,
    tmax: int,
    use_kernel: bool = False,
    block_g: int = 64,
    compact: bool = True,
):
    """``trie_level_advance`` with the parent-frontier gather fused into
    the jitted program (cell i seeds from the previous level's cell
    ``cells[i, 1]``) - one dispatch and one host upload per level
    instead of four eager gathers plus three uploads plus the advance.
    """
    pidx = cells[:, 1]
    seed = (prev_phi[pidx], prev_psi[pidx], prev_valid[pidx],
            prev_ovf[pidx])
    return trie_level_advance_ref(
        tokens, order, start, count, *seed, cells[:, 0], cells[:, 2:],
        emax=emax, tmax=tmax, use_kernel=use_kernel, block_g=block_g,
        compact=compact,
    )


@functools.partial(jax.jit, static_argnames=("n_label_keys",))
def index_and_node_prescreen(tokens, node_req, *, n_label_keys: int):
    """Inverted token index plus the per-node residual-``req`` prescreen
    (trie.py): ``possible[b, n] = counts_b >= node_req_n`` elementwise.
    Monotone up the trie, so a failing node prunes its whole subtree at
    its highest failing ancestor."""
    order, start, count = build_token_index(
        tokens, n_label_keys=n_label_keys
    )
    possible = (count[:, None, :] >= node_req[None, :, :]).all(-1)
    return order, start, count, possible


@functools.partial(
    jax.jit,
    static_argnames=("ni", "nv", "emax", "tmax", "use_kernel", "block_n"),
)
def fused_trie_walk(
    tokens, order, start, count,  # tokens + prebuilt inverted index
    cells,      # [N, 2] int32: (sequence index, packed subtree index)
    steps_s,    # [Sp, Nmax, STEP_FIELDS] int32 (SubtreePack.steps)
    parent_s,   # [Sp, Nmax] int32 (SubtreePack.parent)
    req_s,      # [Sp, Nmax, K] int32 (SubtreePack.pack_req)
    *,
    ni: int,
    nv: int,
    emax: int,
    tmax: int,
    use_kernel: bool = False,
    block_n: int = 8,
):
    """The fused megakernel's serving entry point: walk N (sequence,
    depth-1 subtree) cells through their *entire* subtree in one jitted
    program - the per-cell gathers (the sequence's token table + index
    rows by ``cells[:, 0]``, the packed subtree tables by
    ``cells[:, 1]``) are fused in front of the walk, so the whole batch
    costs a single dispatch regardless of trie depth.  Returns
    ``(acc [N, Nmax] bool, ovf_term [N, Nmax] bool)`` per subtree slot,
    bit-identical to the per-level ``trie_root_advance`` /
    ``trie_level_advance_gather`` ladder (kernels.trie_walk.ref has the
    exact contract).  ``ni`` must be the *global* trie depth (same as
    the per-level path) for bitwise frontier-state identity."""
    cell_b = cells[:, 0]
    s_idx = cells[:, 1]
    tokens = tokens.astype(jnp.int32)
    tok_c = tokens[cell_b]
    order_c = order[cell_b]
    start_c = start[cell_b]
    count_c = count[cell_b]
    steps_c = steps_s[s_idx]
    parent_c = parent_s[s_idx]
    req_c = req_s[s_idx]
    if use_kernel:
        acc, ovft = trie_walk_blocked(
            tok_c, order_c, start_c, count_c, steps_c, parent_c, req_c,
            emax=emax, tmax=tmax, ni=ni, nv=nv, block_n=block_n,
        )
        return acc > 0, ovft > 0
    return trie_walk_core(
        tok_c, order_c, start_c, count_c, steps_c, parent_c, req_c,
        emax=emax, tmax=tmax, ni=ni, nv=nv,
    )


def trie_contains_ref(
    tokens,          # [B, T, 6] int32 (encode_db layout)
    lvl_steps,       # [D, Mh, STEP_FIELDS] int32 (TrieLevels.steps)
    lvl_parent_pos,  # [D, Mh] int32
    term_level,      # [P] int32 (TrieLevels.term_level)
    term_pos,        # [P] int32
    pattern_valid,   # [P] int32
    *,
    nv: int,
    n_label_keys: int,
    emax: int = 8,
    tmax: int = 16,
    use_kernel: bool = False,
    block_g: int = 64,
):
    """Dense level-synchronous trie containment: every (sequence, trie
    node) cell advances once per level, seeded from its parent's
    compacted frontier; pattern answers are read off at their terminal
    (level, position).  Unjitted body, traceable inside shard_map - use
    ``trie_contains`` standalone.  Bit-identical to ``batch_contains``
    over the same bank.  Returns (contained [B,P] bool, ovf [B,P] bool).
    """
    B = tokens.shape[0]
    D, Mh, _ = lvl_steps.shape
    P = pattern_valid.shape[0]
    NI = D  # a pattern has at most as many itemsets as trie levels
    tokens = tokens.astype(jnp.int32)
    lvl_steps = lvl_steps.astype(jnp.int32)
    order, start, count = build_token_index(
        tokens, n_label_keys=n_label_keys
    )
    cell_b = jnp.repeat(jnp.arange(B, dtype=jnp.int32), Mh)
    # virtual root level: one root embedding per sequence
    phi, psi, valid, _ = trie_root_state(B, NI, nv)
    phi = phi[:, None]          # [B, Mprev=1, Ein=1, NI]
    psi = psi[:, None]
    valid = valid[:, None]
    ovf = jnp.zeros((B, 1), jnp.bool_)
    accs, ovfs = [], []
    for d in range(D):
        pp = lvl_parent_pos[d]  # [Mh] (all zeros on level 0)
        seed_phi = phi[:, pp].reshape(B * Mh, *phi.shape[2:])
        seed_psi = psi[:, pp].reshape(B * Mh, *psi.shape[2:])
        seed_valid = valid[:, pp].reshape(B * Mh, valid.shape[2])
        seed_ovf = ovf[:, pp].reshape(B * Mh)
        step_d = jnp.broadcast_to(
            lvl_steps[d][None], (B, Mh, lvl_steps.shape[2])
        ).reshape(B * Mh, lvl_steps.shape[2])
        if d == D - 1:
            # the deepest level is all leaves: skip compaction but keep
            # the compacted path's frontier-overflow semantics so the
            # dense outputs stay bit-identical to batch_contains
            accepted, lovf = trie_level_advance_ref(
                tokens, order, start, count,
                seed_phi, seed_psi, seed_valid, seed_ovf,
                cell_b, step_d, emax=emax, tmax=tmax,
                use_kernel=use_kernel, block_g=block_g, compact=False,
                count_frontier_ovf=True,
            )
        else:
            # dense outputs use the full path overflow (ovf_state) for
            # terminals too: that is what batch_contains reports (its
            # unpadded final steps run compaction), and the dense
            # contract is bit-identity with it
            nphi, npsi, nvalid, accepted, lovf, _ = \
                trie_level_advance_ref(
                    tokens, order, start, count,
                    seed_phi, seed_psi, seed_valid, seed_ovf,
                    cell_b, step_d, emax=emax, tmax=tmax,
                    use_kernel=use_kernel, block_g=block_g,
                    compact=True,
                )
            phi = nphi.reshape(B, Mh, *nphi.shape[1:])
            psi = npsi.reshape(B, Mh, *npsi.shape[1:])
            valid = nvalid.reshape(B, Mh, nvalid.shape[1])
            ovf = lovf.reshape(B, Mh)
        accs.append(accepted.reshape(B, Mh))
        ovfs.append(lovf.reshape(B, Mh))
    if not accs:  # empty trie: nothing is ever contained
        zero = jnp.zeros((B, P), jnp.bool_)
        return zero, zero
    A = jnp.stack(accs)   # [D, B, Mh]
    O = jnp.stack(ovfs)
    real = (pattern_valid > 0)[None, :]
    contained = A[term_level, :, term_pos].T & real
    overflow = O[term_level, :, term_pos].T & real
    return contained, overflow


trie_contains = functools.partial(
    jax.jit,
    static_argnames=(
        "nv", "n_label_keys", "emax", "tmax", "use_kernel", "block_g",
    ),
)(trie_contains_ref)


def batch_contains_ref(
    tokens,         # [B, T, 6] int32 (encode_db layout)
    steps,          # [P, L, STEP_FIELDS] int32 (bank.steps)
    pattern_valid,  # [P] int32 (bank.pattern_valid)
    *,
    nv: int,
    n_label_keys: int,
    emax: int = 8,
    tmax: int = 16,
    use_kernel: bool = False,
    block_g: int = 64,
):
    """Dense batch x bank containment (every cell joined; unjitted body,
    traceable inside shard_map - use ``batch_contains`` standalone).
    Returns (contained [B,P] bool, overflow [B,P] bool)."""
    B = tokens.shape[0]
    P = steps.shape[0]
    order, start, count = build_token_index(
        tokens, n_label_keys=n_label_keys
    )
    cell_b = jnp.repeat(jnp.arange(B, dtype=jnp.int32), P)
    cell_steps = jnp.broadcast_to(
        steps[None], (B,) + steps.shape
    ).reshape(B * P, *steps.shape[1:])
    contained, overflow = _join(
        tokens, order, start, count, cell_b, cell_steps,
        nv=nv, emax=emax, tmax=tmax,
        use_kernel=use_kernel, block_g=block_g,
    )
    real = (pattern_valid > 0)[None, :]
    return (contained.reshape(B, P) & real,
            overflow.reshape(B, P) & real)


batch_contains = functools.partial(
    jax.jit,
    static_argnames=(
        "nv", "n_label_keys", "emax", "tmax", "use_kernel", "block_g",
    ),
)(batch_contains_ref)
