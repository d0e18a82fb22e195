"""The benchmark's own generators: deterministic by seed, and of the sizes
their configuration states."""
import hashlib
import json
import os

import numpy as np
import pytest

from bench import graphs, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


BIG_SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def youtube():
    return config("youtube-rmat16")


def test_rmat_has_the_configured_size(youtube):
    u, v, n = graphs.check_graph(youtube, BIG_SEED)
    assert n == youtube["vertices"] == 2**16
    assert u.size == youtube["undirected_edges"]
    assert np.all(u < v) and v.max() < n                 # no loops, in range
    assert np.unique(u * n + v).size == u.size           # no duplicates
    # the published mean degree is kept
    pub = youtube["published"]
    assert u.size / n == pytest.approx(
        pub["undirected_edges"] / pub["vertices"], rel=1e-4)


def test_rmat_is_deterministic_by_seed(youtube):
    a = graphs.check_graph(youtube, 7)
    b = graphs.check_graph(youtube, 7)
    c = graphs.check_graph(youtube, 8)
    d = spec.generator("rmat")(youtube, graphs.stream(7, 1))
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])
    assert not np.array_equal(a[1], d[1])


def test_closed_loop_solves_the_same_graphs_in_a_seeded_order(youtube):
    traffic = {"graphs": 5}
    a = graphs.closed_order(youtube, traffic, BIG_SEED)
    b = graphs.closed_order(youtube, traffic, BIG_SEED)
    c = graphs.closed_order(youtube, traffic, 3)
    key = lambda gs: sorted(u[:50].tolist() + v[:50].tolist() for u, v, _ in gs)  # noqa: E731
    assert [np.array_equal(x[1], y[1]) for x, y in zip(a, b)] == [True] * 5
    assert key(a) == key(c)                      # the same five graphs
    assert [x[1][:50].tolist() for x in a] != [x[1][:50].tolist() for x in c]


def test_check_graph_is_drawn_from_the_seed_in_the_timed_shape(youtube):
    """The graph judged after the window: new data on every seed, of the
    shape the timed graphs have, so it runs the same compiled programs."""
    timed = graphs.closed_order(youtube, {"graphs": 2}, BIG_SEED)
    checks = [graphs.check_graph(youtube, s) for s in (BIG_SEED, 3, 4)]
    for u, v, n in checks:
        assert (u.size, n) == (timed[0][0].size, timed[0][2])
    keys = {v.tobytes() for _, v, _ in checks + timed}
    assert len(keys) == 5


def test_rmat_keeps_the_graph500_skew(youtube):
    u, v, n = graphs.check_graph(youtube, 3)
    deg = np.bincount(np.concatenate([u, v]), minlength=n)
    # heavy tail: the top 1% of vertices hold far more than 1% of degree
    top = np.sort(deg)[-n // 100:].sum() / deg.sum()
    assert top > 0.1


def _sha(g) -> str:
    u, v, n = g
    h = hashlib.sha256()
    for part in (u.astype(np.int64).tobytes(), v.astype(np.int64).tobytes(),
                 str(n).encode()):
        h.update(part)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("seed, timed, check", [
    (0, ["fef39a269a5fbc2a", "f34e4110c8ae33b3"], "4f19951798ad5c70"),
    (2900000011, ["f34e4110c8ae33b3", "fef39a269a5fbc2a"], "2af6892398dfb4f4"),
])
def test_youtube_cell_solves_the_graphs_it_always_has(youtube, seed, timed,
                                                      check):
    """The cell's inputs, pinned: the timed graphs in the seed's order and
    the graph drawn from the seed, as sha256 over ``u``, ``v`` (int64) and
    ``n``, as the harness made them before generators were found by
    ``kind``."""
    traffic = spec.traffic("louvain")
    assert [_sha(g) for g in graphs.closed_order(youtube, traffic, seed)] \
        == timed
    assert _sha(graphs.check_graph(youtube, seed)) == check


def test_unknown_kind_names_the_file_it_looked_for(youtube):
    with pytest.raises(FileNotFoundError, match="generators/nothing.py"):
        graphs.check_graph(dict(youtube, kind="nothing"), 1)
