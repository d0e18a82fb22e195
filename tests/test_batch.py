"""Batched many-graph engine (DESIGN.md §Serving).

The contract under test, in order of importance:

  1. **Parity** — for every graph in a batch, ``louvain_batch``/``plp_batch``
     return results bit-identical to the single-graph drivers (the
     capacity-portability contract extended over the vmap batch axis), on
     every backend including the documented pallas→ell vmap fallback.
  2. **Bucketing** — ``capacity_signature`` quantizes arbitrary graph sizes
     onto the menu anchored at the cascade floors; realistic many-graph
     workloads land on a handful of buckets (≤4 at the default menus).
  3. **Program reuse** — same-signature traffic hits the bounded LRU
     program caches; the caches expose stats and honor their maxsize.
  4. **Robustness** — degenerate graphs (zero-capacity, all-isolates)
     flow through a batch without poisoning their batch-mates, with the
     PR-7 per-graph RunReport discipline intact.
"""
import jax
import numpy as np
import pytest

from repro.core import progcache
from repro.core.batch import MAX_SLOTS, louvain_batch, plp_batch
from repro.core.louvain import LouvainConfig, louvain
from repro.core.plp import PLPConfig, plp
from repro.graph import packing
from repro.graph.builders import from_numpy_edges
from repro.graph.generators import rmat, sbm
from repro.kernels.common import bucket_capacity, capacity_signature
from repro.utils import telemetry

E = np.zeros(0, np.int64)


def _sbm_graphs(sizes, seed0=0):
    gs = []
    for i, n in enumerate(sizes):
        u, v, _w, _t = sbm(n, 4, p_in=0.3, p_out=0.02, seed=seed0 + i)
        gs.append(from_numpy_edges(u, v, n=n))
    return gs


# ----------------------------------------------------------------- signature


def test_bucket_capacity_menu():
    assert bucket_capacity(1, 64) == 64
    assert bucket_capacity(64, 64) == 64
    assert bucket_capacity(65, 64) == 128
    assert bucket_capacity(5000, 64) == 8192
    with pytest.raises(ValueError):
        bucket_capacity(-1, 64)


def test_capacity_signature_quantizes_and_schedules():
    a = capacity_signature(100, 900)
    b = capacity_signature(120, 1000)
    assert a == b                      # same bucket despite different sizes
    assert a.n_cap == 128 and a.m_cap == 1024
    assert a.ell_width > 0
    assert isinstance(a.schedule, tuple)
    big = capacity_signature(5000, 200000)
    assert big.n_cap > a.n_cap and len(big.schedule) >= 1


def test_realistic_workloads_land_on_few_buckets():
    """Planted-partition ego-net stand-ins (the serving workload) and an
    R-MAT sweep each land on a handful of buckets at the default menus —
    the serving premise that makes request batching effective."""
    egonets = _sbm_graphs([30, 40, 45, 55, 60, 40, 35, 50, 60, 30])
    sigs = {capacity_signature(g.n_max, g.m_max) for g in egonets}
    assert len(sigs) <= 4, sorted(sigs)
    rmats = []
    for scale in (6, 7, 8):
        u, v, _w = rmat(scale, 8, seed=scale)
        rmats.append(from_numpy_edges(u, v, n=1 << scale))
    rsigs = {capacity_signature(g.n_max, g.m_max) for g in rmats}
    assert len(rsigs) <= 4, sorted(rsigs)


class _Compiles:
    """Counts jaxprs traced and executables compiled while the block runs
    (JAX's monitoring events)."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax

        self.traces = self.compiles = 0
        self._listener = self._on
        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self

    def _on(self, event, duration, **_):
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listener)


def test_dispatches_of_one_to_eight_graphs_share_one_program():
    """Every dispatch of a signature runs its ``dispatch_width`` lanes (8
    here), so chunks of 1 to 8 graphs of one signature share ONE compiled
    program: one miss in the program cache, and nothing traced or compiled
    after the first."""
    gs = []
    for i in range(MAX_SLOTS):
        u, v, _w, _t = sbm(40 + 7 * i, 4, p_in=0.3, p_out=0.02, seed=300 + i)
        gs.append(from_numpy_edges(u, v, n=40 + 7 * i, n_max=128,
                                   m_max=4096))
    assert len({capacity_signature(g.n_max, g.m_max) for g in gs}) == 1
    cfg = LouvainConfig(seed=11)    # a spec no other test compiles
    misses0 = progcache.cache_stats()["batch.louvain"]["misses"]
    first = louvain_batch(gs[:1], cfg)
    assert progcache.cache_stats()["batch.louvain"]["misses"] == misses0 + 1
    with _Compiles() as c:
        out = [louvain_batch(gs[:k], cfg) for k in range(1, MAX_SLOTS + 1)]
    assert (c.traces, c.compiles) == (0, 0)
    assert progcache.cache_stats()["batch.louvain"]["misses"] == misses0 + 1
    assert np.array_equal(out[0][0].labels, first[0].labels)
    for k, res in enumerate(out, start=1):
        assert len(res) == k
        assert np.array_equal(res[-1].labels, louvain(gs[k - 1], cfg).labels)



def test_wide_signatures_run_fewer_lanes():
    """``dispatch_width``: ``max_slots`` lanes while a dispatch carries at
    most ``LANE_ENTRIES`` edge entries, fewer for wider signatures, at
    least one; a bucket of a wide signature is chunked at that width and
    its filler lanes are counted."""
    from repro.core.batch import LANE_ENTRIES, dispatch_width

    widths = {m: dispatch_width(capacity_signature(256, m))
              for m in (256, 8192, 16384, 32768, 1 << 20)}
    assert widths == {256: MAX_SLOTS, 8192: 8, 16384: 4, 32768: 2,
                      1 << 20: 1}
    for m, w in widths.items():
        assert w == 1 or w * capacity_signature(256, m).m_cap <= LANE_ENTRIES
    assert dispatch_width(capacity_signature(64, 256), max_slots=3) == 3
    gs = []
    for i in range(5):
        u, v, _w, _t = sbm(60 + 9 * i, 4, p_in=0.3, p_out=0.02, seed=700 + i)
        gs.append(from_numpy_edges(u, v, n=60 + 9 * i, n_max=256,
                                   m_max=16384))
    c0 = {k: telemetry.get(k) for k in ("batch.lanes", "batch.empty_lanes",
                                        "batch.louvain_dispatches")}
    out = louvain_batch(gs)
    grew = {k: telemetry.get(k) - c0[k] for k in c0}
    assert grew == {"batch.lanes": 8, "batch.empty_lanes": 3,
                    "batch.louvain_dispatches": 2}
    for g, res in zip(gs, out):
        assert np.array_equal(res.labels, louvain(g).labels)


# ------------------------------------------------------------------- packing


def test_pad_graph_grow_only_and_parity():
    g = _sbm_graphs([50])[0]
    p = packing.pad_graph(g, 256, 2048)
    assert (p.n_max, p.m_max) == (256, 2048)
    assert int(p.n_valid) == int(g.n_valid)
    assert int(p.m_valid) == int(g.m_valid)
    # padded run is bit-identical on valid vertices (capacity portability)
    r0 = louvain(g)
    r1 = louvain(p)
    assert np.array_equal(r0.labels, r1.labels[:g.n_max])
    assert r0.modularity == r1.modularity
    with pytest.raises(ValueError):
        packing.pad_graph(p, 128, 2048)


def _device_padded(u, v, w, n, n_cap, m_cap):
    """Ingest as it was before padding moved to the host: the canonical
    arrays moved to the device and padded there to the edge count
    (``graph_from_arrays``), then re-padded to the bucket (``pad_graph``),
    with eager ``jnp`` ops."""
    import jax.numpy as jnp

    from repro.graph.builders import _symmetrize_sorted

    src, dst, ww, n = _symmetrize_sorted(u, v, w, n, True, "src")
    m = src.shape[0]
    m_max = max(m, 1)
    sent = jnp.int32(n)
    src = jnp.concatenate([jnp.asarray(src, jnp.int32),
                           jnp.full((m_max - m,), sent)])
    dst = jnp.concatenate([jnp.asarray(dst, jnp.int32),
                           jnp.full((m_max - m,), sent)])
    ww = jnp.concatenate([jnp.asarray(ww, jnp.float32),
                          jnp.zeros((m_max - m,), jnp.float32)])
    mask = jnp.arange(m_max) < m
    cap = jnp.int32(n_cap)
    pad = m_cap - m_max
    return dict(
        src=jnp.concatenate([jnp.where(mask, src, cap),
                             jnp.full((pad,), cap)]),
        dst=jnp.concatenate([jnp.where(mask, dst, cap),
                             jnp.full((pad,), cap)]),
        w=jnp.concatenate([jnp.where(mask, ww, 0.0),
                           jnp.zeros((pad,), jnp.float32)]),
        edge_mask=jnp.concatenate([mask, jnp.zeros((pad,), bool)]),
        n_valid=jnp.int32(n), m_valid=jnp.int32(m), n_max=n_cap,
        m_max=m_cap, sorted_by="src")


@pytest.mark.parametrize("case", ["sbm", "loops_and_duplicates", "weighted",
                                  "isolates", "no_edges"])
def test_host_padded_ingest_equals_device_padded_graph(case):
    """``from_numpy_edges`` padded on the host straight to a bucket gives,
    field by field (values, dtypes, sentinels, static meta), the graph
    that device-side padding gave; the device copy moves each array once
    and runs no program."""
    rng = np.random.default_rng(7)
    n, w = 50, None
    if case == "sbm":
        u, v, _w, _t = sbm(n, 4, p_in=0.3, p_out=0.02, seed=400)
    elif case == "loops_and_duplicates":
        u = np.array([0, 1, 1, 3, 3, 0, 7, 7])
        v = np.array([1, 0, 1, 3, 4, 1, 2, 2])
    elif case == "weighted":
        u = rng.integers(0, n, 300)
        v = rng.integers(0, n, 300)
        w = rng.random(300) * 3.0
    elif case == "isolates":
        u, v = np.array([0, 2]), np.array([1, 3])
    else:
        u = v = E
    sig = capacity_signature(n, max(2 * len(u), 1))
    want = _device_padded(u, v, w, n, sig.n_cap, sig.m_cap)
    with _Compiles() as c:
        for device in (False, True):
            g = from_numpy_edges(u, v, w, n=n, n_max=sig.n_cap,
                                 m_max=sig.m_cap, device=device)
            for f, x in want.items():
                got = getattr(g, f)
                if isinstance(x, (int, str)):
                    assert got == x, f
                    continue
                assert np.asarray(got).dtype == x.dtype, f
                assert np.asarray(got).shape == x.shape, f
                assert np.array_equal(np.asarray(got), np.asarray(x)), f
    assert c.compiles == 0
    assert isinstance(g.src, jax.Array)
    # without a capacity: the unpadded graph it always was
    plain = from_numpy_edges(u, v, w, n=n)
    assert (plain.n_max, plain.m_max) == (n, max(int(plain.m_valid), 1))


def test_stack_graphs_validates():
    a, b = _sbm_graphs([50, 80])
    with pytest.raises(ValueError):
        packing.stack_graphs([a, b])   # capacity mismatch
    pa = packing.pad_graph(a, 256, 2048)
    pb = packing.pad_graph(b, 256, 2048)
    gb = packing.stack_graphs([pa, pb])
    assert gb.src.shape == (2, 2048)
    assert gb.n_valid.shape == (2,)
    assert gb.n_max == 256


# -------------------------------------------------------------------- parity


BACKENDS = ("segment", "ell", "pallas")


@pytest.mark.parametrize("backend", BACKENDS)
def test_louvain_batch_parity(backend):
    """Batched results are bit-identical to the unbatched driver per graph
    (mixed sizes → multiple buckets in one call)."""
    gs = _sbm_graphs([40, 90, 150, 300, 60])
    cfg = LouvainConfig(backend=backend)
    batched = louvain_batch(gs, cfg)
    for g, r in zip(gs, batched):
        u = louvain(g, cfg)
        assert np.array_equal(r.labels, u.labels)
        assert r.modularity == u.modularity
        assert r.levels == u.levels
        assert r.n_communities == u.n_communities
        assert r.sweeps_per_level == u.sweeps_per_level
        assert r.modularity_history == u.modularity_history
        assert r.delta_n_per_level == u.delta_n_per_level
        # watchdog/precision warnings are part of parity; the static
        # pallas→ell fallback is telemetry, never a degradation
        assert r.run_report.warnings == u.run_report.warnings
        assert r.run_report.degradations == []


@pytest.mark.parametrize("backend", BACKENDS)
def test_plp_batch_parity(backend):
    gs = _sbm_graphs([40, 90, 150, 300, 60], seed0=10)
    cfg = PLPConfig(backend=backend)
    batched = plp_batch(gs, cfg)
    for g, r in zip(gs, batched):
        u = plp(g, cfg)
        assert np.array_equal(r.labels, u.labels)
        assert r.iterations == u.iterations
        assert r.delta_n_history == u.delta_n_history
        assert r.active_history == u.active_history


def test_batch_padding_slots_do_not_change_results():
    """Results are invariant to batch-mates and slot padding: a graph
    clustered alone, in a ragged batch, and in a full batch gets identical
    labels (vmap-lane independence)."""
    gs = _sbm_graphs([70, 70, 70, 70, 70], seed0=20)
    alone = louvain_batch(gs[:1])[0]       # 1 graph, 7 filler lanes
    ragged = louvain_batch(gs[:3])[0]      # 3 graphs, 5 filler lanes
    full = louvain_batch(gs)[0]            # 5 graphs, 3 filler lanes
    assert np.array_equal(alone.labels, ragged.labels)
    assert np.array_equal(alone.labels, full.labels)
    assert alone.modularity == ragged.modularity == full.modularity


def test_leiden_batch_parity():
    gs = _sbm_graphs([60, 120], seed0=30)
    cfg = LouvainConfig(refine=True)
    batched = louvain_batch(gs, cfg)
    for g, r in zip(gs, batched):
        u = louvain(g, cfg)
        assert np.array_equal(r.labels, u.labels)
        assert r.modularity == u.modularity


def test_lane_scheduling_orders_chunks_and_preserves_parity():
    """Per-bucket lane scheduling (``_schedule_lanes``): lanes are ordered
    descending by predicted sweep cost before chunking, heavy graphs land
    in the front chunks, and — the contract that matters — per-graph
    results are bit-identical with scheduling on, off, and unbatched."""
    from repro.core.batch import _chunks, _schedule_lanes
    from repro.utils import telemetry

    # one shared signature (pinned capacities), heterogeneous sizes so the
    # heuristic has real work: interleave heavy and light graphs
    sizes = [30, 110, 25, 100, 35, 120, 40, 90, 28, 105]
    gs = []
    for i, n in enumerate(sizes):
        u, v, _w, _t = sbm(n, 4, p_in=0.3, p_out=0.02, seed=200 + i)
        gs.append(from_numpy_edges(u, v, n=128, m_max=2048))
    assert len({capacity_signature(g.n_max, g.m_max) for g in gs}) == 1

    order = _schedule_lanes(gs, list(range(len(gs))))
    mvs = [int(gs[i].m_valid) for i in order]
    assert mvs == sorted(mvs, reverse=True)       # densest first
    # with max_slots=4, every chunk's heaviest lane ≥ next chunk's heaviest
    chunks = list(_chunks(order, 4))
    heaviest = [max(int(gs[i].m_valid) for i in c) for c in chunks]
    assert heaviest == sorted(heaviest, reverse=True)

    cfg = LouvainConfig()
    before = telemetry.get("batch.lane_scheduled_buckets")
    scheduled = louvain_batch(gs, cfg, max_slots=4)
    assert telemetry.get("batch.lane_scheduled_buckets") > before
    unscheduled = louvain_batch(gs, cfg, max_slots=4, lane_schedule=False)
    for g, rs, ru in zip(gs, scheduled, unscheduled):
        u = louvain(g, cfg)
        assert np.array_equal(rs.labels, u.labels)
        assert np.array_equal(ru.labels, u.labels)
        assert rs.modularity == ru.modularity == u.modularity
        assert rs.sweeps_per_level == u.sweeps_per_level
        assert rs.delta_n_per_level == u.delta_n_per_level

    pcfg = PLPConfig()
    p_sched = plp_batch(gs, pcfg, max_slots=4)
    p_plain = plp_batch(gs, pcfg, max_slots=4, lane_schedule=False)
    for g, rs, ru in zip(gs, p_sched, p_plain):
        u = plp(g, pcfg)
        assert np.array_equal(rs.labels, u.labels)
        assert np.array_equal(ru.labels, u.labels)
        assert rs.iterations == ru.iterations == u.iterations


# ------------------------------------------------------------- program cache


def test_same_signature_hits_program_cache():
    """Same-signature traffic reuses the compiled batch program: after a
    warm call, a second batch with DIFFERENT graphs of the same signature
    adds zero cache misses (the zero-steady-state-recompile contract)."""
    def gs(sizes, seed0):
        # pin capacities so both waves provably share one bucket signature
        out = []
        for i, n in enumerate(sizes):
            u, v, _w, _t = sbm(n, 4, p_in=0.3, p_out=0.02, seed=seed0 + i)
            out.append(from_numpy_edges(u, v, n=100, m_max=1000))
        return out

    cfg = LouvainConfig()
    louvain_batch(gs([50, 80], seed0=40), cfg)               # warm
    info0 = progcache.cache_stats()["batch.louvain"]
    louvain_batch(gs([66, 99], seed0=50), cfg)               # same signature
    info1 = progcache.cache_stats()["batch.louvain"]
    assert info1["misses"] == info0["misses"]
    assert info1["hits"] > info0["hits"]


def test_cache_stats_exposes_bounded_caches():
    """Every compiled-program cache is registered, observable, and bounded
    (satellite: the formerly-unbounded lru_caches now declare a maxsize)."""
    stats = progcache.cache_stats()
    for name in ("batch.louvain", "batch.plp", "engine.fused_phase",
                 "engine.step", "engine.distributed_phase", "louvain.stage",
                 "louvain.shrink"):
        assert name in stats, name
        assert stats[name]["maxsize"] is not None
        assert stats[name]["maxsize"] > 0


# ---------------------------------------------------------------- degenerate


def test_batch_with_empty_graph_slot():
    """A zero-capacity graph in a batch short-circuits to the trivial
    result (PR-7 contract) without poisoning its batch-mates."""
    gs = _sbm_graphs([60], seed0=60)
    empty = from_numpy_edges(E, E, n=0)
    mixed = [gs[0], empty, gs[0]]
    out = louvain_batch(mixed)
    assert out[1].labels.shape == (0,)
    assert out[1].n_communities == 0
    assert out[1].modularity == 0.0
    assert out[1].run_report.clean
    oracle = louvain(gs[0])
    for r in (out[0], out[2]):
        assert np.array_equal(r.labels, oracle.labels)
        assert r.modularity == oracle.modularity

    pout = plp_batch(mixed)
    assert pout[1].labels.shape == (0,)
    assert pout[1].iterations == 0
    p_oracle = plp(gs[0])
    assert np.array_equal(pout[0].labels, p_oracle.labels)


def test_batch_with_all_isolates_slot():
    """All-isolated-vertices graphs (0 edges, n > 0) batch cleanly next to
    normal graphs and keep their singleton answer."""
    iso = from_numpy_edges(E, E, n=5)
    gs = _sbm_graphs([60], seed0=70)
    out = louvain_batch([iso, gs[0]])
    oracle_iso = louvain(iso)
    assert np.array_equal(out[0].labels, oracle_iso.labels)
    assert out[0].n_communities == 5
    assert out[0].modularity == 0.0
    assert np.array_equal(out[1].labels, louvain(gs[0]).labels)


# ------------------------------------------------------------------- service


def test_serve_engine_end_to_end():
    from launch.community_serve import (CommunityRequest,
                                        CommunityServeEngine)

    eng = CommunityServeEngine()
    sizes = [50, 80, 120, 50]
    for i, n in enumerate(sizes):
        u, v, _w, _t = sbm(n, 4, p_in=0.3, p_out=0.02, seed=80 + i)
        eng.submit(CommunityRequest(request_id=f"r{i}", u=u, v=v, n=n,
                                    algo="plp" if i == 3 else "louvain"))
    # poisoned request: rejected at ingest, never joins a batch
    eng.submit(CommunityRequest(
        request_id="bad", u=np.array([0, 1]), v=np.array([1, 2]),
        w=np.array([np.nan, 1.0])))
    assert eng.pending() == 4
    resp = eng.flush()
    assert [r.request_id for r in resp] == ["r0", "r1", "r2", "r3", "bad"]
    by_id = {r.request_id: r for r in resp}
    assert not by_id["bad"].ok and "InputValidationError" in by_id["bad"].error
    for i, n in enumerate(sizes):
        r = by_id[f"r{i}"]
        assert r.ok and r.labels.shape == (n,)
        assert r.latency_s > 0 and r.batch_size >= 1
    # bitwise parity through the whole service path
    u, v, _w, _t = sbm(50, 4, p_in=0.3, p_out=0.02, seed=80)
    assert np.array_equal(by_id["r0"].labels,
                          louvain(from_numpy_edges(u, v, n=50)).labels)
    stats = eng.stats()
    assert stats["served"] == 4
    assert stats["pending"] == 0
    assert "batch.louvain" in stats["programs"]
    assert stats["counters"].get("serve.ingest_reject", 0) >= 1
    # a second flush serves fresh same-signature traffic from cache
    misses0 = eng.stats()["programs"]["batch.louvain"]["misses"]
    u, v, _w, _t = sbm(66, 4, p_in=0.3, p_out=0.02, seed=99)
    eng.submit(CommunityRequest(request_id="r9", u=u, v=v, n=66))
    r9 = eng.flush()[0]
    assert r9.ok
    assert eng.stats()["programs"]["batch.louvain"]["misses"] == misses0


def test_a_symmetric_request_lands_in_its_one_sided_bucket():
    """Requests are bucketed on their canonical sizes: one that lists both
    directions of every edge, and some edges twice, lands in the bucket of
    its one-sided form (its raw size, ``2·len(u)``, is a bucket up) and is
    served in the same dispatch."""
    from launch.community_serve import (CommunityRequest,
                                        CommunityServeEngine, ingest_request)

    u, v, _w, _t = sbm(90, 4, p_in=0.3, p_out=0.02, seed=610)
    one = CommunityRequest(request_id="one", u=u, v=v, n=90)
    both = CommunityRequest(request_id="both", u=np.concatenate([u, v, u[:9]]),
                            v=np.concatenate([v, u, v[:9]]), n=90)
    g1, _ = ingest_request(one)
    g2, _ = ingest_request(both)
    sig = capacity_signature(g1.n_max, g1.m_max)
    assert (g2.n_max, g2.m_max) == (g1.n_max, g1.m_max) == (sig.n_cap,
                                                            sig.m_cap)
    assert int(g2.m_valid) == int(g1.m_valid)
    assert capacity_signature(90, 2 * len(both.u)).m_cap > sig.m_cap
    eng = CommunityServeEngine()
    eng.submit(one)
    eng.submit(both)
    d0 = telemetry.get("batch.louvain_dispatches")
    r1, r2 = eng.flush()
    assert telemetry.get("batch.louvain_dispatches") - d0 == 1
    assert r1.ok and r2.ok and r1.signature == r2.signature == tuple(sig)
    assert r2.labels.shape == (90,)


def test_second_edge_count_in_a_bucket_compiles_nothing():
    """Requests are ingested on the host straight to their bucket, so once
    a signature's batch program exists a request of another edge count in
    that bucket traces and compiles nothing, from submit to response."""
    from launch.community_serve import (CommunityRequest,
                                        CommunityServeEngine, ingest_request)

    def request_signature(req):
        g, _ = ingest_request(req)
        return capacity_signature(g.n_max, g.m_max)

    eng = CommunityServeEngine()
    reqs = []
    for i, (n, p_in) in enumerate([(70, 0.3), (90, 0.25), (100, 0.2)]):
        u, v, _w, _t = sbm(n, 4, p_in=p_in, p_out=0.02, seed=500 + i)
        reqs.append(CommunityRequest(request_id=f"e{i}", u=u, v=v, n=n))
    sigs = {request_signature(r) for r in reqs}
    assert len(sigs) == 1 and len({len(r.u) for r in reqs}) == 3
    eng.submit(reqs[0])
    first = eng.flush()[0]
    with _Compiles() as c:
        for r in reqs[1:]:
            eng.submit(r)
        rest = eng.flush()
    assert (c.traces, c.compiles) == (0, 0)
    for r, resp in zip(reqs, [first] + rest):
        assert resp.ok and resp.labels.shape == (r.n,)
        assert resp.signature == tuple(request_signature(r))
        single = louvain(from_numpy_edges(r.u, r.v, n=r.n))
        assert np.array_equal(resp.labels, single.labels)
        assert resp.result.modularity == single.modularity
