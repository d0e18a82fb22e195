"""Segment / GroupBy primitives — the XLA re-expression of Arkouda's GroupBy.

The paper's aggregation phase leans on Arkouda ``GroupBy`` + ``Broadcast``
(§III-B2).  On TPU the same computation is a stable lexicographic sort
(``sort_by_keys``) followed by run detection (`run_starts`), run-id `cumsum`, and
``segment_sum`` — every helper here is jit-safe with static shapes.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp


def sort_by_keys(
    keys: Sequence[jax.Array], values: Sequence[jax.Array] = ()
) -> Tuple[Tuple[jax.Array, ...], Tuple[jax.Array, ...]]:
    """Stable lexicographic sort of ``values`` by ``keys`` (all same length).

    One pass per key, least significant first, each sorting (key,
    position) pairs by both: the current position breaks ties, so every
    pass is stable and the result is the order of one stable multi-key
    sort.  Every pass is the same two-operand sort, which the TPU compiler
    builds once: for v5e at 12.4M entries one stable sort over 3 keys + 1
    value took 394 s to compile, a stable two-operand sort 63 s and this
    one 33 s (JAX 0.9.0, libtpu 0.0.34, compiled on an 8-core x86 host).
    The price is run time: on one v5e at 1,524,310 entries this takes
    129 ms against 6.3 ms for the one stable sort, the per-pass gathers
    being the cost."""
    pos = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    perm = pos
    for k in reversed(tuple(keys)):
        _, order = jax.lax.sort((k[perm], pos), num_keys=2, is_stable=False)
        perm = perm[order]
    return tuple(k[perm] for k in keys), tuple(v[perm] for v in values)


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

    Invalid entries must already sort to the end (give them sentinel keys).

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
    if valid is None:
        valid = jnp.ones((m,), dtype=bool)
    flag = jnp.where(valid, 0, 1).astype(jnp.int32)
    (sk, sv) = sort_by_keys((flag,) + tuple(keys), (values,))
    sflag, *skeys = sk
    svalid = sflag == 0
    starts_all = run_starts(sflag, *skeys)
    starts = starts_all & svalid
    rid = run_ids(starts_all)
    sums = jax.ops.segment_sum(jnp.where(svalid, sv[0], 0.0), rid, num_segments=m)
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
