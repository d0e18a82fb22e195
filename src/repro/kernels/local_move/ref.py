"""Pure-jnp oracle for the fused gather-in-kernel local-move kernels.

The contract shared with kernel.py: per row r (one vertex, ELL tile of width
W), gather the per-vertex tables at the row/neighbor ids, then score the
move — the PLP weighted label mode or the Louvain Eq. 1 ΔQ argmax — and emit
the per-row ``(proposal, propose)`` pair directly.

Tables are the (n+1)-entry "extended" arrays the sweep engine builds once per
sweep: slot ``sentinel`` (= n) is the padding sink, so ``labels_ext[n] = n``,
``vol_ext[n] = size_ext[n] = deg_ext[n] = 0``.  Row/neighbor ids are in
[0, n] with n marking padding.  Every table access goes through ``_gather``,
which masks sentinel ids to the sink VALUE explicitly instead of reading the
sink slot — so the same scoring code runs against the full resident table
(``win_lo=None``) or against a streamed window slice rebased by ``win_lo``
(DESIGN.md §Kernels): real ids are guaranteed inside the window by the host
window metadata, sentinel ids never touch the table at all.  Resident and
windowed evaluation are therefore bit-identical by construction.

Louvain's Eq. 1 terms are community-indexed (volCom/sizeCom of the CANDIDATE
community), which a window over vertex ids cannot bound.
``compose_louvain_tables`` folds that second-level gather into per-VERTEX
tables once per sweep (``volcom_v[v] = vol_ext[com_ext[v]]`` …), so the
per-neighbor kernel gathers are all vertex-indexed and window-friendly;
the composed values are the exact floats the two-level gather produced.

The scoring math is delegated to the label_argmax / delta_q oracles so this
ref stays bit-compatible with the legacy gather-outside two-step by
construction (same gather expressions, same reductions, same tie-breaks).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.delta_q.ref import delta_q_ref
from repro.kernels.label_argmax.ref import label_argmax_ref


def _gather(tab: jax.Array, ids: jax.Array, sentinel: int, fill,
            win_lo: Optional[jax.Array]) -> jax.Array:
    """Masked (optionally window-rebased) table gather.

    ``ids`` are vertex ids in [0, n]; real ids (< n = sentinel) must lie in
    [win_lo, win_lo + len(tab)) — guaranteed for windows by the host
    metadata, trivially for the full table.  Sentinel/padding ids take the
    table's documented sink-slot VALUE (``fill``) without reading the table,
    so the clip below never leaks an out-of-window read into the result.
    """
    idx = ids if win_lo is None else ids - win_lo
    idx = jnp.clip(idx, 0, tab.shape[0] - 1)
    return jnp.where(ids < sentinel, tab[idx], fill)


def plp_gather(rows, nbr, labels_ext, *, sentinel: int,
               win_lo: Optional[jax.Array] = None):
    """The PLP move's table reads: (nbr_lab[R, W], cur_lab[R], rows_n[R])."""
    n = sentinel
    nbr_lab = _gather(labels_ext, nbr, n, n, win_lo)
    cur_lab = _gather(labels_ext, rows, n, n, win_lo)
    return nbr_lab, cur_lab, jnp.where(rows < n, rows, n)


def plp_score(nbr_lab, w, cur_lab, rows_n, seed, *, tie_eps: float,
              sentinel: int,
              argmax=label_argmax_ref) -> Tuple[jax.Array, jax.Array]:
    """(best_label[R], propose[R]) from gathered tiles — the body of every
    PLP layout.  ``argmax`` is ``label_argmax_ref`` or, for the gathered
    TPU layout, its Pallas kernel (same signature, bit-identical)."""
    best_lab, best_score, cur_score = argmax(
        nbr_lab, w, cur_lab, rows_n, seed, tie_eps, sentinel
    )
    return best_lab, (best_lab >= 0) & (best_score > cur_score)


def local_move_plp_ref(
    rows: jax.Array,        # (R,) int32 vertex id per row (sentinel = pad)
    nbr: jax.Array,         # (R, W) int32 neighbor ids (sentinel = pad)
    w: jax.Array,           # (R, W) float32 edge weights (0 = pad)
    labels_ext: jax.Array,  # (n+1,) int32 full table, labels_ext[n] = n —
                            # or a window slice of it when win_lo is given
    seed: jax.Array,        # uint32 scalar tie-noise seed
    *,
    tie_eps: float,
    sentinel: int,
    win_lo: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(best_label[R], propose[R]) for the PLP move, gathers included."""
    tiles = plp_gather(rows, nbr, labels_ext, sentinel=sentinel, win_lo=win_lo)
    return plp_score(tiles[0], w, *tiles[1:], seed, tie_eps=tie_eps,
                     sentinel=sentinel)


def compose_louvain_tables(
    com_ext: jax.Array,   # (n+1,) int32 community per vertex, com_ext[n] = n
    vol_ext: jax.Array,   # (n+1,) float32 community volume, vol_ext[n] = 0
    size_ext: jax.Array,  # (n+1,) int32 community size, size_ext[n] = 0
    deg_ext: jax.Array,   # (n+1,) float32 weighted degree, deg_ext[n] = 0
    sentinel: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-VERTEX composed tables (com_v, volcom_v, sizecom_v, deg_v).

    ``volcom_v[v] = vol_ext[com_ext[v]]`` etc: one (n+1,) gather per sweep
    that turns every community-indexed Eq. 1 term into a vertex-indexed one,
    so the kernels gather by row/neighbor id only.  The sink contract is
    preserved: com_ext[n] = n ⇒ volcom_v[n] = vol_ext[n] = 0 (same for
    size), so composed tables carry the same sink values the two-level
    gather produced.
    """
    idx = jnp.clip(com_ext, 0, sentinel)
    return com_ext, vol_ext[idx], size_ext[idx], deg_ext


def louvain_gather(rows, nbr, com_v, volcom_v, sizecom_v, deg_v, *,
                   sentinel: int, win_lo: Optional[jax.Array] = None):
    """The Eq. 1 table reads on vertex-composed tables: (cand[R, W],
    cur[R], deg[R], vol_cand[R, W], vol_cur[R], size_cand[R, W],
    size_cur[R])."""
    n = sentinel
    return (
        _gather(com_v, nbr, n, n, win_lo),
        _gather(com_v, rows, n, n, win_lo),
        _gather(deg_v, rows, n, 0.0, win_lo),
        _gather(volcom_v, nbr, n, 0.0, win_lo),
        _gather(volcom_v, rows, n, 0.0, win_lo),
        _gather(sizecom_v, nbr, n, 0, win_lo),
        _gather(sizecom_v, rows, n, 0, win_lo),
    )


def louvain_score(cand, w, cur, deg, vol_cand, vol_cur, size_cand, size_cur,
                  inv_vol, *, sentinel: int, singleton_rule: bool,
                  argmax=delta_q_ref) -> Tuple[jax.Array, jax.Array]:
    """(best_community[R], propose[R]) from gathered tiles — the body of
    every Louvain layout; the gain test is Eq. 1 > 0.  ``argmax`` is
    ``delta_q_ref`` or, for the gathered TPU layout, its Pallas kernel."""
    best_cand, best_gain = argmax(
        cand, w, cur, deg, vol_cand, vol_cur, size_cand, size_cur, inv_vol,
        sentinel=sentinel, singleton_rule=singleton_rule,
    )
    return best_cand, (best_cand >= 0) & (best_gain > 0.0)


def local_move_louvain_tables_ref(
    rows: jax.Array,       # (R,) int32 vertex id per row (sentinel = pad)
    nbr: jax.Array,        # (R, W) int32 neighbor ids (sentinel = pad)
    w: jax.Array,          # (R, W) float32 edge weights (0 = pad)
    com_v: jax.Array,      # (n+1,) int32 community per vertex, com_v[n] = n
    volcom_v: jax.Array,   # (n+1,) f32 vol of v's community, volcom_v[n] = 0
    sizecom_v: jax.Array,  # (n+1,) i32 size of v's community, sizecom_v[n]=0
    deg_v: jax.Array,      # (n+1,) f32 weighted degree, deg_v[n] = 0
    inv_vol: jax.Array,    # f32 scalar 1 / vol(V)
    *,
    sentinel: int,
    singleton_rule: bool,
    win_lo: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(best_community[R], propose[R]) on vertex-composed tables (Eq. 1).

    The single scoring path shared by the resident kernel (full tables,
    ``win_lo=None``), the streamed kernel (window slices + rebase), the
    gathered TPU layout (``louvain_score`` over the ``delta_q`` kernel) and
    the pure-jnp windowed ref — kernel ≡ ref holds structurally.
    """
    g = louvain_gather(rows, nbr, com_v, volcom_v, sizecom_v, deg_v,
                       sentinel=sentinel, win_lo=win_lo)
    return louvain_score(g[0], w, *g[1:], inv_vol, sentinel=sentinel,
                         singleton_rule=singleton_rule)


def local_move_louvain_ref(
    rows: jax.Array,      # (R,) int32 vertex id per row (sentinel = pad)
    nbr: jax.Array,       # (R, W) int32 neighbor ids (sentinel = pad)
    w: jax.Array,         # (R, W) float32 edge weights (0 = pad)
    com_ext: jax.Array,   # (n+1,) int32 community per vertex, com_ext[n] = n
    vol_ext: jax.Array,   # (n+1,) float32 community volume, vol_ext[n] = 0
    size_ext: jax.Array,  # (n+1,) int32 community size, size_ext[n] = 0
    deg_ext: jax.Array,   # (n+1,) float32 weighted degree, deg_ext[n] = 0
    inv_vol: jax.Array,   # f32 scalar 1 / vol(V)
    *,
    sentinel: int,
    singleton_rule: bool,
) -> Tuple[jax.Array, jax.Array]:
    """(best_community[R], propose[R]) on community-indexed tables.

    Convenience wrapper: compose the per-vertex tables, then score.  Values
    are identical to the historical two-level gather
    (``vol_ext[com_ext[nbr]]`` = ``volcom_v[nbr]`` elementwise).
    """
    tabs = compose_louvain_tables(com_ext, vol_ext, size_ext, deg_ext, sentinel)
    return local_move_louvain_tables_ref(
        rows, nbr, w, *tabs, inv_vol,
        sentinel=sentinel, singleton_rule=singleton_rule,
    )
