"""Aggregation edge cases + GroupBy compaction equivalence.

Covers the paper's Alg. 3 aggregation phase where the pipeline loop leans on
it hardest: all-intra partitions (coarse graph collapses to pure self-loops),
all-invalid levels (masked-out graphs), the one-sort scatter compaction in
``graph/segment.py::groupby_sum`` vs the legacy two-sort argsort path, the
FUSED one-sort ``remap_and_coarsen`` vs the two-step reference (bit-for-bit,
the §Pipeline one-sort coarsening invariant), the capacity-changing
``shrink_graph`` compaction the cascade descends through, and the SORT-FREE
binned path (DESIGN.md §Aggregation kernel): bitmap-cumsum remap + hash-bin
scatter merge vs the one-sort oracle, bit-for-bit, across multigraphs,
forced-overflow fallbacks, capacity-padded graphs, every cascade stage
capacity, the Pallas rank kernel vs its jnp ref, and end-to-end
louvain/leiden runs under ``aggregation="binned"`` vs ``"sort"``.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import aggregation
from repro.core.modularity import modularity
from repro.graph import segment as seg
from repro.graph.builders import from_numpy_edges
from repro.graph.generators import ring_of_cliques, sbm
from repro.graph.structure import Graph, graph_from_arrays


# ------------------------------------------------------------ edge cases


def test_coarsen_all_intra_edges_become_self_loops():
    """Aggregating by a partition with NO cut edges: every coarse edge is a
    self-loop and the vol/deg/modularity invariants survive exactly."""
    k = 5
    u, v, w, gt = ring_of_cliques(6, k)
    # drop the ring edges so ground-truth communities are fully intra
    keep = (u // k) == (v // k)
    g = from_numpy_edges(u[keep], v[keep], w[keep], n=len(gt))
    com = jnp.asarray(np.concatenate(
        [gt, np.arange(len(gt), g.n_max)]), jnp.int32)

    new_com, n_comm = aggregation.remap_communities(com, g.vertex_mask())
    cg = aggregation.coarsen_graph(g, new_com, n_comm)

    assert int(n_comm) == 6
    # every surviving coarse edge is a self-loop
    em = np.asarray(cg.edge_mask)
    assert em.sum() == 6
    np.testing.assert_array_equal(
        np.asarray(cg.src)[em], np.asarray(cg.dst)[em])
    # volume invariant: total directed weight (2W) is preserved
    assert float(cg.total_volume()) == pytest.approx(
        float(g.total_volume()), rel=1e-6)
    # degree invariant: coarse deg(c) == sum of member degrees (community vol)
    deg = np.asarray(g.weighted_degrees())
    vol_c = np.zeros(g.n_max, np.float64)
    np.add.at(vol_c, np.asarray(new_com)[: len(gt)], deg[: len(gt)])
    np.testing.assert_allclose(
        np.asarray(cg.weighted_degrees())[: int(n_comm)],
        vol_c[: int(n_comm)], rtol=1e-6)
    # modularity invariant: Q(fine, partition) == Q(coarse, identity)
    ident = jnp.arange(cg.n_max, dtype=jnp.int32)
    q_fine = float(modularity(g, new_com))
    q_coarse = float(modularity(cg, ident))
    assert q_fine == pytest.approx(q_coarse, abs=1e-6)
    # all-intra partition of a disconnected union of cliques: Q = 1 - sum s_c^2
    assert q_fine == pytest.approx(1.0 - 6 * (1.0 / 6) ** 2, abs=1e-5)


def test_coarsen_preserves_modularity_with_cut_edges():
    u, v, w, gt = sbm(120, 4, p_in=0.4, p_out=0.05, seed=13)
    g = from_numpy_edges(u, v, w)
    com = jnp.asarray(np.concatenate(
        [gt, np.arange(len(gt), g.n_max)]), jnp.int32)
    new_com, n_comm = aggregation.remap_communities(com, g.vertex_mask())
    cg = aggregation.coarsen_graph(g, new_com, n_comm)
    ident = jnp.arange(cg.n_max, dtype=jnp.int32)
    assert float(modularity(cg, ident)) == pytest.approx(
        float(modularity(g, new_com)), abs=1e-6)
    assert float(cg.total_volume()) == pytest.approx(
        float(g.total_volume()), rel=1e-6)


def _empty_graph(n_max=16, m_max=32) -> Graph:
    """A fully masked-out level: zero valid vertices, zero valid edges."""
    sentinel = jnp.int32(n_max)
    return Graph(
        src=jnp.full((m_max,), sentinel),
        dst=jnp.full((m_max,), sentinel),
        w=jnp.zeros((m_max,), jnp.float32),
        edge_mask=jnp.zeros((m_max,), bool),
        n_valid=jnp.int32(0),
        m_valid=jnp.int32(0),
        n_max=n_max,
        m_max=m_max,
        sorted_by=None,
    )


def test_remap_and_coarsen_all_invalid_level():
    """An all-masked-invalid level must stay a well-formed empty graph:
    no phantom communities, no phantom edges, zero volumes/degrees."""
    g = _empty_graph()
    com = jnp.arange(g.n_max, dtype=jnp.int32)
    new_com, n_comm = aggregation.remap_communities(com, g.vertex_mask())
    assert int(n_comm) == 0
    # every vertex slot maps to the sentinel
    np.testing.assert_array_equal(
        np.asarray(new_com), np.full(g.n_max, g.n_max, np.int32))

    cg = aggregation.coarsen_graph(g, new_com, n_comm)
    assert int(cg.n_valid) == 0
    assert int(cg.m_valid) == 0
    assert not bool(np.asarray(cg.edge_mask).any())
    assert float(cg.total_volume()) == 0.0
    np.testing.assert_array_equal(
        np.asarray(cg.weighted_degrees()), np.zeros(g.n_max, np.float32))
    # invalid slots hold sentinels, preserving the Graph convention
    np.testing.assert_array_equal(
        np.asarray(cg.src), np.full(g.m_max, g.n_max, np.int32))


def test_coarsen_partially_masked_vertices():
    """Vertices beyond n_valid are excluded from the coarse graph even if
    stray (masked) edges mention them."""
    u = np.array([0, 1, 2, 3], dtype=np.int64)
    v = np.array([1, 0, 3, 2], dtype=np.int64)
    w = np.ones(4, dtype=np.float32)
    g = graph_from_arrays(jnp.asarray(u, jnp.int32), jnp.asarray(v, jnp.int32),
                          jnp.asarray(w), n_max=8, m_max=8, n_valid=4)
    com = jnp.asarray([0, 0, 1, 1, 7, 7, 7, 7], jnp.int32)
    new_com, n_comm = aggregation.remap_communities(com, g.vertex_mask())
    assert int(n_comm) == 2
    cg = aggregation.coarsen_graph(g, new_com, n_comm)
    assert int(cg.n_valid) == 2
    em = np.asarray(cg.edge_mask)
    assert set(map(tuple, np.stack(
        [np.asarray(cg.src)[em], np.asarray(cg.dst)[em]], axis=1))) == {
            (0, 0), (1, 1)}
    assert float(cg.total_volume()) == pytest.approx(4.0)


# ------------------------------------------------------------ fused one-sort


def _coarsen_two_step(g, com):
    new_com, n_comm = aggregation.remap_communities(com, g.vertex_mask())
    return new_com, n_comm, aggregation.coarsen_graph(g, new_com, n_comm)


def _assert_graphs_bitwise(a, b):
    for f in ("src", "dst", "w", "edge_mask"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)), err_msg=f)
    assert int(a.n_valid) == int(b.n_valid)
    assert int(a.m_valid) == int(b.m_valid)
    assert (a.n_max, a.m_max) == (b.n_max, b.m_max)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_remap_and_coarsen_matches_two_step(seed):
    """The fused one-sort remap+coarsen must reproduce the two-step
    reference bit-for-bit: new_com, n_comm, and every coarse-graph array
    including the unspecified-slot sentinels."""
    u, v, w, gt = sbm(150, 5, p_in=0.3, p_out=0.04, seed=seed)
    g = from_numpy_edges(u, v, w, m_max=2 * len(u) + 37)   # padded capacity
    rng = np.random.default_rng(seed)
    # a messy, non-contiguous partition (not the ground truth): random
    # labels drawn from a sparse id set, plus junk on the invalid slots
    com = jnp.asarray(np.concatenate([
        rng.choice(np.arange(0, 150, 7), size=150),
        rng.integers(0, g.n_max, size=g.n_max - 150),
    ]), jnp.int32)
    nc1, n1, cg1 = _coarsen_two_step(g, com)
    nc2, n2, cg2 = aggregation.remap_and_coarsen(g, com)
    assert int(n1) == int(n2)
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc2))
    _assert_graphs_bitwise(cg1, cg2)


def test_remap_and_coarsen_all_intra_and_empty():
    # all-intra: pure self-loops (mirrors the two-step edge-case test)
    k = 5
    u, v, w, gt = ring_of_cliques(6, k)
    keep = (u // k) == (v // k)
    g = from_numpy_edges(u[keep], v[keep], w[keep], n=len(gt))
    com = jnp.asarray(np.concatenate(
        [gt, np.arange(len(gt), g.n_max)]), jnp.int32)
    nc1, n1, cg1 = _coarsen_two_step(g, com)
    nc2, n2, cg2 = aggregation.remap_and_coarsen(g, com)
    assert int(n1) == int(n2) == 6
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc2))
    _assert_graphs_bitwise(cg1, cg2)

    # fully masked-out level
    ge = _empty_graph()
    com = jnp.arange(ge.n_max, dtype=jnp.int32)
    nc2, n2, cg2 = aggregation.remap_and_coarsen(ge, com)
    assert int(n2) == 0
    assert int(cg2.m_valid) == 0
    assert not bool(np.asarray(cg2.edge_mask).any())
    np.testing.assert_array_equal(
        np.asarray(nc2), np.full(ge.n_max, ge.n_max, np.int32))


def test_shrink_graph_preserves_live_content():
    """Capacity descent: slicing a front-compacted coarse graph must keep
    every live edge/vertex and only rewrite the padding sentinels."""
    u, v, w, gt = sbm(120, 4, p_in=0.4, p_out=0.05, seed=13)
    g = from_numpy_edges(u, v, w)
    com = jnp.asarray(np.concatenate(
        [gt, np.arange(len(gt), g.n_max)]), jnp.int32)
    _, n_comm, cg = aggregation.remap_and_coarsen(g, com)
    n_out = int(n_comm) + 2
    m_out = int(cg.m_valid) + 3
    sg = aggregation.shrink_graph(cg, n_out, m_out)
    assert (sg.n_max, sg.m_max) == (n_out, m_out)
    assert int(sg.n_valid) == int(cg.n_valid)
    assert int(sg.m_valid) == int(cg.m_valid)
    mv = int(cg.m_valid)
    for f in ("src", "dst", "w"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sg, f))[:mv], np.asarray(getattr(cg, f))[:mv])
    em = np.asarray(sg.edge_mask)
    np.testing.assert_array_equal(
        np.asarray(sg.src)[~em], np.full((~em).sum(), n_out, np.int32))
    assert float(sg.total_volume()) == float(cg.total_volume())
    # modularity invariant survives the capacity change
    ident = jnp.arange(sg.n_max, dtype=jnp.int32)
    assert float(modularity(sg, ident)) == pytest.approx(
        float(modularity(g, jnp.asarray(
            np.asarray(aggregation.remap_communities(
                com, g.vertex_mask())[0]), jnp.int32))), abs=1e-6)


# ------------------------------------------------------------ groupby compaction


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_groupby_sum_scatter_matches_argsort(seed):
    """The one-sort scatter compaction must agree with the legacy two-sort
    argsort compaction on every valid slot (slots beyond n_groups are
    unspecified by contract and masked by group_valid)."""
    rng = np.random.default_rng(seed)
    m = 257
    k1 = jnp.asarray(rng.integers(0, 12, m), jnp.int32)
    k2 = jnp.asarray(rng.integers(0, 7, m), jnp.int32)
    vals = jnp.asarray(rng.standard_normal(m), jnp.float32)
    valid = jnp.asarray(rng.random(m) < 0.8)

    (ka, sa, va, na) = seg.groupby_sum((k1, k2), vals, valid=valid,
                                       compact_via="argsort")
    (kb, sb, vb, nb) = seg.groupby_sum((k1, k2), vals, valid=valid,
                                       compact_via="scatter")
    n = int(na)
    assert n == int(nb)
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    for a, b in zip(ka, kb):
        np.testing.assert_array_equal(np.asarray(a)[:n], np.asarray(b)[:n])
    # sums agree bitwise on the valid prefix (same sort, same segment_sum)
    np.testing.assert_array_equal(np.asarray(sa)[:n], np.asarray(sb)[:n])


def test_groupby_sum_matches_numpy_reference():
    rng = np.random.default_rng(3)
    m = 200
    k = rng.integers(0, 15, m)
    vals = rng.standard_normal(m).astype(np.float32)
    valid = rng.random(m) < 0.7
    (gk,), gs, gv, ng = seg.groupby_sum(
        (jnp.asarray(k, jnp.int32),), jnp.asarray(vals),
        valid=jnp.asarray(valid))
    expect = {}
    for ki, vi, ok in zip(k, vals, valid):
        if ok:
            expect[int(ki)] = expect.get(int(ki), 0.0) + float(vi)
    n = int(ng)
    assert n == len(expect)
    got = {int(a): float(b) for a, b in
           zip(np.asarray(gk)[:n], np.asarray(gs)[:n])}
    assert set(got) == set(expect)
    for key in expect:
        assert got[key] == pytest.approx(expect[key], abs=1e-5)


def test_groupby_sum_all_invalid():
    m = 33
    (gk,), gs, gv, ng = seg.groupby_sum(
        (jnp.zeros((m,), jnp.int32),), jnp.ones((m,), jnp.float32),
        valid=jnp.zeros((m,), bool))
    assert int(ng) == 0
    assert not bool(np.asarray(gv).any())


# ------------------------------------------------------------ sort_by_keys

I32_MAX = np.iinfo(np.int32).max


def _lsd_sort_by_keys(keys, values=()):
    """The earlier ``sort_by_keys``: one (key, position) sort per key, least
    significant first, the permutation applied by gathers.  Kept here only
    as the reference the one-sort version must equal bit for bit."""
    pos = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    perm = pos
    for k in reversed(tuple(keys)):
        _, order = jax.lax.sort((k[perm], pos), num_keys=2, is_stable=False)
        perm = perm[order]
    return tuple(k[perm] for k in keys), tuple(v[perm] for v in values)


def _flag_key_groupby_sum(keys, values, valid=None):
    """The earlier ``groupby_sum``: validity as a leading sort key, over
    ``_lsd_sort_by_keys``; the reference for the folded-validity version."""
    m = values.shape[0]
    if valid is None:
        valid = jnp.ones((m,), dtype=bool)
    flag = jnp.where(valid, 0, 1).astype(jnp.int32)
    (sk, sv) = _lsd_sort_by_keys((flag,) + tuple(keys), (values,))
    sflag, *skeys = sk
    svalid = sflag == 0
    starts_all = seg.run_starts(sflag, *skeys)
    starts = starts_all & svalid
    rid = seg.run_ids(starts_all)
    sums = jax.ops.segment_sum(jnp.where(svalid, sv[0], 0.0), rid,
                               num_segments=m)
    n_groups = jnp.sum(starts.astype(jnp.int32))
    group_valid = jnp.arange(m, dtype=jnp.int32) < n_groups
    pos = jnp.where(starts, rid, m)
    idx = (jnp.zeros((m + 1,), jnp.int32)
           .at[pos].set(jnp.arange(m, dtype=jnp.int32), mode="drop")[:m])
    return tuple(k[idx] for k in skeys), sums, group_valid, n_groups


def _sort_case(case, rng):
    """int32 key columns for one ``sort_by_keys`` case."""
    if case == "one_key":
        return [rng.integers(0, 50, 300)]
    if case == "two_keys":
        return [rng.integers(0, 40, 300), rng.integers(0, 40, 300)]
    if case == "three_keys":
        return [rng.integers(-20, 20, 400) for _ in range(3)]
    if case == "heavy_ties":
        return [rng.integers(0, 3, 500) for _ in range(3)]
    if case == "sentinel_and_int32_max":
        n = 64     # the sentinel of a 64-vertex graph
        pool = np.array([0, 1, n - 1, n, I32_MAX - 1, I32_MAX])
        return [rng.choice(pool, 300), rng.choice(pool, 300)]
    if case == "length_one":
        return [np.array([I32_MAX]), np.array([3])]
    if case == "all_equal":
        return [np.full(200, 7) for _ in range(3)]
    raise ValueError(case)


SORT_CASES = ["one_key", "two_keys", "three_keys", "heavy_ties",
              "sentinel_and_int32_max", "length_one", "all_equal"]


@pytest.mark.parametrize("n_values", [1, 2])
@pytest.mark.parametrize("case", SORT_CASES)
def test_sort_by_keys_matches_numpy_lexsort(case, n_values):
    """The same permutation as numpy's stable ``lexsort``, with the keys
    sorted and every value carried bit for bit (float32 values include NaN
    payloads and -0.0, which only an untouched move keeps)."""
    rng = np.random.default_rng(SORT_CASES.index(case))
    keys = [k.astype(np.int32) for k in _sort_case(case, rng)]
    m = keys[0].shape[0]
    ints = rng.integers(np.iinfo(np.int32).min, I32_MAX, m, dtype=np.int64)
    floats = rng.standard_normal(m).astype(np.float32)
    floats[::7] = -0.0
    floats.view(np.uint32)[3::11] = 0x7FC00001 + np.arange(
        floats[3::11].size, dtype=np.uint32)     # distinct NaN payloads
    values = [ints.astype(np.int32), floats][:n_values]

    sk, sv = seg.sort_by_keys([jnp.asarray(k) for k in keys],
                              [jnp.asarray(v) for v in values])
    perm = np.lexsort(keys[::-1])     # numpy: last key is the primary one
    assert len(sk) == len(keys) and len(sv) == n_values
    for got, k in zip(sk, keys):
        np.testing.assert_array_equal(np.asarray(got), k[perm])
    for got, v in zip(sv, values):
        assert np.asarray(got).dtype == v.dtype
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                      v[perm].view(np.uint32))
    # the order the earlier per-key passes gave, too
    lk, lv = _lsd_sort_by_keys([jnp.asarray(k) for k in keys],
                               [jnp.asarray(v) for v in values])
    for a, b in zip(sk + sv, lk + lv):
        np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                      np.asarray(b).view(np.uint32))


@pytest.mark.parametrize("with_valid", [False, True])
def test_groupby_sum_folded_validity_matches_numpy(with_valid):
    """Invalid entries hold ordinary (non-sentinel) keys; their groups must
    vanish, the valid groups come out in key order with their sums, and
    the valid prefix equals the earlier flag-key GroupBy bit for bit."""
    rng = np.random.default_rng(11)
    m = 400
    k1 = rng.integers(0, 9, m).astype(np.int32)
    k2 = rng.integers(0, 5, m).astype(np.int32)
    vals = rng.standard_normal(m).astype(np.float32)
    valid = rng.random(m) < 0.6 if with_valid else np.ones(m, bool)
    jvalid = jnp.asarray(valid) if with_valid else None

    (g1, g2), gs, gv, ng = seg.groupby_sum(
        (jnp.asarray(k1), jnp.asarray(k2)), jnp.asarray(vals), valid=jvalid)
    expect = {}
    for a, b, x, ok in zip(k1, k2, vals, valid):
        if ok:
            expect[(int(a), int(b))] = expect.get((int(a), int(b)), 0.0) + float(x)
    n = int(ng)
    assert n == len(expect)
    np.testing.assert_array_equal(np.asarray(gv), np.arange(m) < n)
    got_keys = list(zip(np.asarray(g1)[:n].tolist(), np.asarray(g2)[:n].tolist()))
    assert got_keys == sorted(expect)
    for key, s in zip(got_keys, np.asarray(gs)[:n]):
        assert float(s) == pytest.approx(expect[key], abs=1e-5)

    (r1, r2), rs, rv, rn = _flag_key_groupby_sum(
        (jnp.asarray(k1), jnp.asarray(k2)), jnp.asarray(vals), valid=jvalid)
    assert int(rn) == n
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(gv))
    np.testing.assert_array_equal(np.asarray(r1)[:n], np.asarray(g1)[:n])
    np.testing.assert_array_equal(np.asarray(r2)[:n], np.asarray(g2)[:n])
    np.testing.assert_array_equal(np.asarray(rs)[:n].view(np.uint32),
                                  np.asarray(gs)[:n].view(np.uint32))


@pytest.mark.parametrize("algorithm", ["louvain", "plp"])
def test_whole_solve_bit_identical_to_per_key_sort(algorithm, monkeypatch):
    """Default ``louvain()`` / ``plp()`` on an R-MAT graph give the same
    labels, sweeps and modularity with the one-sort GroupBy as with the
    earlier per-key-pass sort and flag-key GroupBy patched in."""
    from repro.core import progcache
    from repro.core.louvain import louvain
    from repro.core.plp import plp
    from repro.graph.generators import rmat

    graphs = [from_numpy_edges(*rmat(10, 3, seed=s), n=1 << 10)
              for s in (0, 1)]

    def solve_all():
        progcache.clear_caches()
        jax.clear_caches()
        out = []
        for g in graphs:
            if algorithm == "louvain":
                r = louvain(g)
                out.append((np.asarray(r.labels), list(r.sweeps_per_level),
                            float(r.modularity), list(r.modularity_history)))
            else:
                r = plp(g)
                out.append((np.asarray(r.labels), r.iterations,
                            list(r.delta_n_history), None))
        return out

    new = solve_all()
    monkeypatch.setattr(seg, "sort_by_keys", _lsd_sort_by_keys)
    monkeypatch.setattr(seg, "groupby_sum", _flag_key_groupby_sum)
    old = solve_all()
    monkeypatch.undo()
    progcache.clear_caches()
    jax.clear_caches()
    for (la, *ra), (lb, *rb) in zip(new, old):
        np.testing.assert_array_equal(la, lb)
        assert ra == rb


# ------------------------------------------------------------ sort-free binned


def _random_multigraph(rng, n, m, *, n_pad=0, m_pad=0, mask_p=0.85,
                       weighted=True):
    """A directed multigraph with duplicate/parallel edges, random float
    weights, partial edge masks and capacity padding — the adversarial input
    shape for the binned-vs-sort parity contract."""
    n_max, m_max = n + n_pad, m + m_pad
    src = rng.integers(0, n, m)
    # bias toward duplicates: half the edges reuse an earlier endpoint pair
    dst = rng.integers(0, n, m)
    dup = rng.random(m) < 0.5
    if m > 1:
        j = rng.integers(0, m, m)
        src = np.where(dup, src[j], src)
        dst = np.where(dup, dst[j], dst)
    w = (rng.random(m).astype(np.float32) if weighted
         else np.ones(m, np.float32))
    em = np.zeros(m_max, bool)
    em[:m] = rng.random(m) < mask_p
    pad_i = np.full(m_pad, n_max, np.int32)
    return Graph(
        src=jnp.asarray(np.concatenate([src.astype(np.int32), pad_i])),
        dst=jnp.asarray(np.concatenate([dst.astype(np.int32), pad_i])),
        w=jnp.asarray(np.concatenate([w, np.zeros(m_pad, np.float32)])),
        edge_mask=jnp.asarray(em),
        n_valid=jnp.int32(n), m_valid=jnp.int32(m),
        n_max=n_max, m_max=m_max, sorted_by=None)


def _random_partition(rng, g, groups=None):
    n, n_max = int(g.n_valid), g.n_max
    groups = groups if groups is not None else max(1, n // 3)
    return jnp.asarray(np.concatenate([
        rng.integers(0, groups, n),
        rng.integers(0, n_max, n_max - n),     # junk on invalid slots
    ]), jnp.int32)


def _assert_binned_matches_oracle(g, com, **kw):
    nc1, n1, cg1 = aggregation.remap_and_coarsen(g, com)
    nc2, n2, cg2 = aggregation.remap_and_coarsen_binned(g, com, **kw)
    np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc2))
    assert int(n1) == int(n2)
    _assert_graphs_bitwise(cg1, cg2)


def test_remap_communities_bitmap_matches_sorted():
    """The sort-free (presence bitmap + cumsum) remap must reproduce the
    sorted oracle bit-for-bit, junk-on-invalid-slots included."""
    rng = np.random.default_rng(0)
    for _ in range(10):
        n_max = int(rng.integers(2, 80))
        n = int(rng.integers(0, n_max + 1))
        com = jnp.asarray(rng.integers(0, n_max, n_max), jnp.int32)
        vmask = jnp.asarray(np.arange(n_max) < n)
        nc1, k1 = aggregation.remap_communities_sorted(com, vmask)
        nc2, k2 = aggregation.remap_communities(com, vmask)
        assert int(k1) == int(k2)
        np.testing.assert_array_equal(np.asarray(nc1), np.asarray(nc2))


def test_contiguize_ids_basics():
    table, count = seg.contiguize_ids(
        jnp.asarray([5, 2, 5, 9], jnp.int32),
        jnp.asarray([True, True, True, False]), 10)
    assert int(count) == 2
    got = np.asarray(table)
    assert got[2] == 0 and got[5] == 1
    # absent keys (incl. the masked 9) map to the size sentinel
    assert all(got[k] == 10 for k in range(10) if k not in (2, 5))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("width", [16, 64, None])
def test_binned_matches_oracle_random_multigraphs(seed, width):
    """The sort-free binned coarsening must reproduce the one-sort oracle
    bit-for-bit — parallel edges merged to identical float sums, identical
    slot order and padding sentinels — at every width, including widths
    small enough to trip the overflow fallback."""
    rng = np.random.default_rng(seed)
    for _ in range(4):
        n = int(rng.integers(4, 70))
        m = int(rng.integers(4, 400))
        g = _random_multigraph(rng, n, m, n_pad=int(rng.integers(0, 9)),
                               m_pad=int(rng.integers(0, 17)))
        com = _random_partition(rng, g)
        _assert_binned_matches_oracle(g, com, width=width, impl="ref")


def test_binned_all_intra_and_empty():
    # all-intra partition: pure self-loop coarse graph
    k = 5
    u, v, w, gt = ring_of_cliques(6, k)
    keep = (u // k) == (v // k)
    g = from_numpy_edges(u[keep], v[keep], w[keep], n=len(gt))
    com = jnp.asarray(np.concatenate(
        [gt, np.arange(len(gt), g.n_max)]), jnp.int32)
    _assert_binned_matches_oracle(g, com, impl="ref")

    # fully masked-out level
    ge = _empty_graph()
    _assert_binned_matches_oracle(
        ge, jnp.arange(ge.n_max, dtype=jnp.int32), impl="ref")


def test_binned_capacity_padded_sparse_graph():
    """Capacities far above the live counts (the cascade's padded stages):
    the sentinel/sink routing must keep the parity exact."""
    rng = np.random.default_rng(7)
    g = _random_multigraph(rng, 12, 30, n_pad=100, m_pad=400)
    com = _random_partition(rng, g, groups=5)
    for width in (16, 256):
        _assert_binned_matches_oracle(g, com, width=width, impl="ref")


def test_binned_forced_overflow_takes_sort_fallback():
    """A community with more distinct neighbor communities than the bin
    width must raise the overflow predicate and fall back to the one-sort
    path — bit-for-bit with the oracle either way."""
    from repro.kernels.aggregation.ops import community_edge_keys, insert_bins

    n = 40
    # star: vertex 0's community sees 30 distinct neighbor communities
    src = np.zeros(30, np.int32)
    dst = np.arange(1, 31, dtype=np.int32)
    g = graph_from_arrays(jnp.asarray(src), jnp.asarray(dst),
                          jnp.ones(30, jnp.float32), n_max=n, m_max=80,
                          n_valid=n)
    com = jnp.arange(n, dtype=jnp.int32)   # singletons: out-degree 30 > 16
    new_com, _ = aggregation.remap_communities(com, g.vertex_mask())
    cs, cd = community_edge_keys(g, new_com)
    _, _, overflow, rounds = insert_bins(g, cs, cd, width=16)
    assert bool(overflow)
    assert int(rounds) == 0        # the degree pre-gate skipped probing
    _assert_binned_matches_oracle(g, com, width=16, impl="ref")
    # at width 64 the same graph fits the bins
    _, _, overflow64, _ = insert_bins(g, cs, cd, width=64)
    assert not bool(overflow64)
    _assert_binned_matches_oracle(g, com, width=64, impl="ref")


def test_binned_every_cascade_stage_capacity():
    """Parity at every capacity of the cascade schedule (and so every
    STAGE_WIDTH_MENU pick the capacities induce): shrink a real coarsening
    chain into each stage and compare binned vs oracle there."""
    from repro.core.louvain import auto_capacity_schedule

    u, v, w, gt = sbm(300, 6, p_in=0.3, p_out=0.03, seed=5)
    g = from_numpy_edges(u, v, w)
    sched = auto_capacity_schedule(g.n_max, g.m_max, min_n=0,
                                   n_floor=max(16, g.n_max // 64),
                                   m_floor=max(64, g.m_max // 64))
    assert len(sched) > 1
    rng = np.random.default_rng(5)
    com = jnp.asarray(np.concatenate(
        [gt, np.arange(len(gt), g.n_max)]), jnp.int32)
    _, _, cg = aggregation.remap_and_coarsen(g, com)
    for cap in sched:
        if int(cg.n_valid) > cap[0] or int(cg.m_valid) > cap[1]:
            continue
        cur = (aggregation.shrink_graph(cg, *cap)
               if cap != (cg.n_max, cg.m_max) else cg)
        com_c = _random_partition(rng, cur, groups=max(1, int(cur.n_valid)))
        _assert_binned_matches_oracle(cur, com_c)   # width=None: menu pick
        _assert_binned_matches_oracle(cur, com_c, width=16, impl="ref")


def test_bin_rank_kernel_matches_ref():
    """The Pallas rank kernel (interpret mode off-TPU) must agree with the
    jnp ref on the same post-insert key table — the kernel ≡ ref leg of the
    kernel's by-construction parity contract."""
    from repro.kernels.aggregation.kernel import bin_rank_pallas
    from repro.kernels.aggregation.ops import community_edge_keys, insert_bins
    from repro.kernels.aggregation.ref import bin_rank_ref

    rng = np.random.default_rng(11)
    g = _random_multigraph(rng, 24, 160, n_pad=4, m_pad=8)
    com = _random_partition(rng, g, groups=9)
    new_com, _ = aggregation.remap_communities(com, g.vertex_mask())
    cs, cd = community_edge_keys(g, new_com)
    for width in (64, 128):
        keys, _, overflow, _ = insert_bins(g, cs, cd, width=width)
        assert not bool(overflow)
        kf = keys[:-1]
        cs_c = jnp.clip(cs, 0, g.n_max)
        r_ref = bin_rank_ref(kf, cs_c, cd, width=width, empty=g.n_max)
        r_ker = bin_rank_pallas(kf, cs_c, cd, width=width, empty=g.n_max,
                                interpret=True, row_block=32)
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_ker))


def test_binned_kernel_impl_full_coarsen_matches_ref():
    """binned_coarsen with the Pallas kernel rank pass (interpret mode) must
    equal the oracle too — the end-to-end kernel-impl leg."""
    from repro.kernels import common as kc

    rng = np.random.default_rng(13)
    g = _random_multigraph(rng, 20, 120)
    com = _random_partition(rng, g, groups=7)
    # interpret-mode pallas is slow; force it only for this small case
    orig = kc.default_interpret
    try:
        kc.default_interpret = lambda: True
        _assert_binned_matches_oracle(g, com, width=16, impl="kernel")
    finally:
        kc.default_interpret = orig


def test_aggregation_dispatch_and_config_validation():
    from repro.core.louvain import LouvainConfig

    with pytest.raises(ValueError):
        aggregation.remap_and_coarsen_by("bogus", _empty_graph(),
                                         jnp.zeros((16,), jnp.int32))
    with pytest.raises(ValueError):
        LouvainConfig(aggregation="bogus")
    assert LouvainConfig().aggregation == "binned"
    assert LouvainConfig(aggregation="sort").aggregation == "sort"


@pytest.mark.parametrize("refine", [False, True])
@pytest.mark.parametrize("pipeline_fused", [False, True])
def test_e2e_binned_equals_sort(refine, pipeline_fused):
    """Whole louvain/leiden runs under aggregation="binned" vs "sort" must
    be indistinguishable: labels, Q, and every per-level history."""
    from repro.core.louvain import LouvainConfig, louvain

    u, v, w, _ = sbm(200, 5, p_in=0.3, p_out=0.03, seed=2)
    g = from_numpy_edges(u, v, w)
    cfg = LouvainConfig(refine=refine, pipeline_fused=pipeline_fused, seed=4)
    rb = louvain(g, cfg)
    rs = louvain(g, cfg.replace(aggregation="sort"))
    np.testing.assert_array_equal(rb.labels, rs.labels)
    assert rb.n_communities == rs.n_communities
    assert rb.levels == rs.levels
    assert rb.modularity == rs.modularity
    assert rb.modularity_history == rs.modularity_history
    assert rb.sweeps_per_level == rs.sweeps_per_level
    assert rb.n_comm_per_level == rs.n_comm_per_level


# ------------------------------------------------------------ compact


@pytest.mark.parametrize("seed", [0, 1])
def test_compact_scatter_matches_argsort(seed):
    """The sort-free scatter compaction builds the SAME stable permutation
    the legacy argsort did (full array, not just the valid prefix)."""
    rng = np.random.default_rng(seed)
    m = 131
    mask = jnp.asarray(rng.random(m) < 0.6)
    arrays = (jnp.arange(m, dtype=jnp.int32),
              jnp.asarray(rng.standard_normal(m), jnp.float32))
    out_s, n_s = seg.compact(mask, arrays, via="scatter")
    out_a, n_a = seg.compact(mask, arrays, via="argsort")
    assert int(n_s) == int(n_a)
    for a, b in zip(out_s, out_a):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        seg.compact(mask, arrays, via="bogus")
