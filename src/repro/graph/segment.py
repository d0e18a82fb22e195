"""Segment / GroupBy primitives — the XLA re-expression of Arkouda's GroupBy.

The paper's aggregation phase leans on Arkouda ``GroupBy`` + ``Broadcast``
(§III-B2).  On TPU the same computation is a stable lexicographic sort
(``sort_by_keys``) followed by run detection (`run_starts`), run-id `cumsum`, and
``segment_sum`` — every helper here is jit-safe with static shapes.  The
sort carries its payload as operands: on a TPU a gather over the sorted
length costs far more than the sort itself (``sort_by_keys``).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


def sort_by_keys(
    keys: Sequence[jax.Array], values: Sequence[jax.Array] = ()
) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...]]:
    """Stable lexicographic sort of ``values`` by ``keys`` (all same length).

    One stable ``lax.sort`` whose operands are the keys and the values, so
    the sort moves every array itself and no permutation is applied by a
    gather afterwards.  It replaced one (key, position) sort per key, least
    significant first, whose permutation was applied by gathers.  On one
    TPU v5e, for GroupBy's shape (2 int32 keys + 1 float32 value; JAX
    0.9.0, libtpu 0.0.34), the one sort runs in 1.71 ms at 345,050
    entries and 5.17 ms at 1,524,310, against 32.6 ms and 128.9 ms for the
    per-key passes with their gathers (over a third key, the validity flag
    GroupBy then spent).  The per-key passes carrying every operand in
    place of the gathers ran in 2.24 ms and 8.25 ms.  Compiled alone the
    one sort takes 38.3 s and 43.3 s against 18.1 s and 13.9 s, yet inside
    Louvain's whole program at 345,050 entries the first solve, compile
    included, took 72.8 s against 73.9 s."""
    keys, values = tuple(keys), tuple(values)
    out = jax.lax.sort(keys + values, num_keys=len(keys), is_stable=True)
    return out[:len(keys)], out[len(keys):]


def run_starts(*sorted_keys: jax.Array) -> jax.Array:
    """bool[m]: True at the first element of each equal-key run."""
    m = sorted_keys[0].shape[0]
    neq = jnp.zeros((m - 1,), dtype=bool)
    for k in sorted_keys:
        neq = neq | (k[1:] != k[:-1])
    return jnp.concatenate([jnp.ones((1,), dtype=bool), neq])


def run_ids(starts: jax.Array) -> jax.Array:
    """int32[m]: dense run index (0-based) for each element."""
    return jnp.cumsum(starts.astype(jnp.int32)) - 1


def groupby_sum(
    keys: Sequence[jax.Array],
    values: jax.Array,
    valid: jax.Array | None = None,
    compact_via: str = "scatter",
) -> Tuple[Tuple[jax.Array, ...], jax.Array, jax.Array, jax.Array]:
    """GroupBy(keys).sum(values) with static output capacity.

    ``valid`` spends no sort key: an invalid entry takes its dtype's
    largest value in every key, so it sorts after every valid entry, and a
    valid entry must not hold that value in every key.  Without ``valid``
    every entry is grouped.

    Compaction of run representatives to the front is a ``cumsum(starts)``
    scatter/gather off the already-sorted runs (``compact_via="scatter"``,
    default) — ONE ``lax.sort`` per call.  ``compact_via="argsort"`` keeps the
    legacy second full sort for the aggregation benchmark comparison
    (``benchmarks/run.py level_fusion``); the two agree bit-for-bit on the
    first ``n_groups`` slots (slots beyond ``n_groups`` are unspecified and
    must be masked with ``group_valid``).

    Returns (group_keys, group_sums, group_valid, n_groups):
      group_keys: one representative key tuple per run, COMPACTED to the front
      group_sums: float sums per run, compacted to the front
      group_valid: bool[m] — first n_groups entries True
      n_groups: int32 scalar (number of valid groups)
    """
    m = values.shape[0]
    keys = tuple(keys)
    if valid is not None:
        keys = tuple(jnp.where(valid, k, jnp.iinfo(k.dtype).max) for k in keys)
    skeys, (sv,) = sort_by_keys(keys, (values,))
    starts_all = run_starts(*skeys)
    rid = run_ids(starts_all)
    if valid is None:
        starts = starts_all
    else:
        svalid = jnp.arange(m) < jnp.sum(valid.astype(jnp.int32))
        starts = starts_all & svalid
        sv = jnp.where(svalid, sv, 0.0)
    sums = jax.ops.segment_sum(sv, rid, num_segments=m)
    n_groups = jnp.sum(starts.astype(jnp.int32))
    group_valid = jnp.arange(m, dtype=jnp.int32) < n_groups
    if compact_via == "scatter":
        # Valid runs sort first, so the j-th valid run start has rid == j:
        # scatter each start's position into output slot rid, then gather.
        # Slots >= n_groups keep index 0 (arbitrary; masked by group_valid),
        # and sums is already rid-indexed so it needs no gather at all.
        pos = jnp.where(starts, rid, m)
        idx = (jnp.zeros((m + 1,), jnp.int32)
               .at[pos].set(jnp.arange(m, dtype=jnp.int32), mode="drop")[:m])
        group_keys = tuple(k[idx] for k in skeys)
        group_sums = sums
    elif compact_via == "argsort":
        order = jnp.argsort(jnp.where(starts, 0, 1), stable=True)
        group_keys = tuple(k[order] for k in skeys)
        group_sums = sums[rid[order]]
    else:
        raise ValueError(f"unknown compact_via {compact_via!r}")
    return group_keys, group_sums, group_valid, n_groups


def compact(
    mask: jax.Array,
    arrays: Sequence[jax.Array],
    via: str = "scatter",
) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Stable-move entries where mask is True to the front. Returns (arrays, count).

    ``via="scatter"`` (default) builds the stable permutation with a
    ``cumsum`` + scatter — the same sort-free compaction ``groupby_sum``
    uses — instead of the legacy full ``argsort`` (``via="argsort"``, kept
    for the ``coarse_cascade`` benchmark A/B).  The two permutations are
    identical: True entries land at their True-rank, False entries at
    count + False-rank, both in original order.
    """
    m = mask.shape[0]
    count = jnp.sum(mask.astype(jnp.int32))
    if via == "scatter":
        csum = jnp.cumsum(mask.astype(jnp.int32))
        pos = jnp.where(mask, csum - 1,
                        count + jnp.arange(m, dtype=jnp.int32) - csum)
        perm = (jnp.zeros((m,), jnp.int32)
                .at[pos].set(jnp.arange(m, dtype=jnp.int32)))
    elif via == "argsort":
        perm = jnp.argsort(jnp.where(mask, 0, 1), stable=True)
    else:
        raise ValueError(f"unknown via {via!r}, want 'scatter' or 'argsort'")
    return tuple(a[perm] for a in arrays), count


def contiguize_ids(
    keys: jax.Array, valid: jax.Array, size: int
) -> Tuple[jax.Array, jax.Array]:
    """Sort-free dense-id assignment for integer keys in ``[0, size)``.

    Presence bitmap + ``cumsum`` instead of the historical sort + run-detect
    (the sort-free invariant of DESIGN.md §Pipeline): scatter 1s at the
    present keys, then the exclusive prefix sum over the bitmap IS the dense
    id, ascending in raw-key order — the same deterministic ordering the
    sorted path produced.

    Returns ``(table, count)``: ``table[k]`` is the dense id of raw key
    ``k`` for present keys and the ``size`` sentinel for absent ones
    (``table`` has ``size`` entries); ``count`` is the number of distinct
    present keys.
    """
    idx = jnp.clip(jnp.where(valid, keys, size), 0, size)
    p = jnp.zeros((size + 1,), jnp.int32).at[idx].set(1)[:size]
    table = jnp.where(p == 1, jnp.cumsum(p) - 1, jnp.int32(size))
    return table, jnp.sum(p)


def segment_argmax(
    scores: jax.Array,
    candidates: jax.Array,
    segments: jax.Array,
    num_segments: int,
    valid: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-segment (max score, candidate achieving it; smallest-candidate tie-break).

    scores: f32[m]; candidates: i32[m]; segments: i32[m] in [0, num_segments).
    Returns (best_score[num_segments], best_candidate[num_segments]);
    empty segments get (-inf, -1).
    """
    neg_inf = jnp.float32(-jnp.inf)
    if valid is not None:
        scores = jnp.where(valid, scores, neg_inf)
    best = jax.ops.segment_max(scores, segments, num_segments=num_segments)
    is_best = scores == best[segments]
    big = jnp.int32(2**31 - 1)
    cand_masked = jnp.where(is_best & (scores > neg_inf), candidates, big)
    best_cand = jax.ops.segment_min(cand_masked, segments, num_segments=num_segments)
    best_cand = jnp.where(best_cand == big, -1, best_cand)
    return best, best_cand
