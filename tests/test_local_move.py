"""Parity suite for the fused gather-in-kernel local_move family
(DESIGN.md §Kernels): kernel ≡ ref ≡ legacy two-step ≡ segment evaluator
bit-for-bit, across all bucket widths, tail-heavy layouts, both evaluators,
interpret mode, plus a fused-pipeline end-to-end check.  The windowed
STREAMED table layout must match the resident layout bit-for-bit as well —
across window sizes (degenerate 1-block and window-spans-whole-table
included), with the resident/streamed auto-selection flipping at the
documented VMEM-budget threshold."""
import os

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.engine import EngineSpec, SweepEngine
from repro.graph.builders import from_numpy_edges
from repro.graph.ell import (
    BUCKET_WIDTHS,
    build_ell,
    compute_windows,
    grid_view,
    to_device,
)
from repro.graph.generators import sbm
from repro.kernels import common as kcommon
from repro.kernels.delta_q import ops as dq_ops
from repro.kernels.label_argmax import ops as la_ops
from repro.kernels.local_move import ops as lm_ops


def _graph(seed=13, n=300, k=6):
    u, v, w, _ = sbm(n, k, p_in=0.3, p_out=0.03, seed=seed)
    return from_numpy_edges(u, v, w)


def _tiles(rows, width, n, seed):
    """Random ELL tile + consistent per-vertex tables for kernel-level tests."""
    rng = np.random.default_rng(seed)
    r_ids = np.full(rows, n, np.int32)
    real = rng.random(rows) < 0.9
    r_ids[real] = rng.choice(n, size=int(real.sum()), replace=False)
    nbr = rng.integers(0, n, (rows, width)).astype(np.int32)
    pad = rng.random((rows, width)) < 0.25
    pad[~real] = True
    nbr[pad] = n
    w = np.where(pad, 0.0, rng.random((rows, width))).astype(np.float32)
    labels = rng.integers(0, n, n).astype(np.int32)
    labels_ext = np.concatenate([labels, [n]]).astype(np.int32)
    deg = (rng.random(n) + 0.1).astype(np.float32)
    vol = (rng.random(n) * 5).astype(np.float32)
    size = rng.integers(1, 5, n).astype(np.int32)
    tables = dict(
        com_ext=jnp.asarray(labels_ext),
        vol_ext=jnp.asarray(np.concatenate([vol, [0.0]]).astype(np.float32)),
        size_ext=jnp.asarray(np.concatenate([size, [0]]).astype(np.int32)),
        deg_ext=jnp.asarray(np.concatenate([deg, [0.0]]).astype(np.float32)),
    )
    return (jnp.asarray(r_ids), jnp.asarray(nbr), jnp.asarray(w),
            jnp.asarray(labels_ext), tables)


@pytest.mark.parametrize("width", BUCKET_WIDTHS)
def test_plp_kernel_matches_ref(width):
    rows = 8 if width >= 256 else 32
    n = 64
    r_ids, nbr, w, labels_ext, _ = _tiles(rows, width, n, seed=width)
    kw = dict(tie_eps=0.25, sentinel=n)
    seed = jnp.uint32(7)
    best_k, prop_k = lm_ops.local_move_plp(
        r_ids, nbr, w, labels_ext, seed, use_pallas=True, interpret=True, **kw)
    best_r, prop_r = lm_ops.local_move_plp(
        r_ids, nbr, w, labels_ext, seed, use_pallas=False, **kw)
    np.testing.assert_array_equal(np.asarray(best_k), np.asarray(best_r))
    np.testing.assert_array_equal(np.asarray(prop_k), np.asarray(prop_r))


@pytest.mark.parametrize("width", BUCKET_WIDTHS)
@pytest.mark.parametrize("singleton_rule", [True, False])
def test_louvain_kernel_matches_ref(width, singleton_rule):
    rows = 8 if width >= 256 else 32
    n = 64
    r_ids, nbr, w, _, tables = _tiles(rows, width, n, seed=width + 1)
    kw = dict(sentinel=n, singleton_rule=singleton_rule)
    vol_total = jnp.float32(37.0)
    best_k, prop_k = lm_ops.local_move_louvain(
        r_ids, nbr, w, vol_total=vol_total, use_pallas=True, interpret=True,
        **tables, **kw)
    best_r, prop_r = lm_ops.local_move_louvain(
        r_ids, nbr, w, vol_total=vol_total, use_pallas=False, **tables, **kw)
    np.testing.assert_array_equal(np.asarray(best_k), np.asarray(best_r))
    np.testing.assert_array_equal(np.asarray(prop_k), np.asarray(prop_r))


@pytest.fixture
def tpu_layout(monkeypatch):
    """The pallas path's TPU policy (the gathered layout), with the kernel
    still in interpret mode on this CPU."""
    monkeypatch.setattr(lm_ops, "on_tpu", lambda: True)


@pytest.mark.parametrize("width", BUCKET_WIDTHS)
def test_plp_gathered_layout_matches_ref(width, tpu_layout):
    """The TPU layout: XLA-gathered tiles scored by ``label_argmax_pallas``
    (``delta_q_pallas`` for Louvain)."""
    rows = 8 if width >= 256 else 32
    n = 64
    r_ids, nbr, w, labels_ext, _ = _tiles(rows, width, n, seed=width + 2)
    kw = dict(tie_eps=0.25, sentinel=n)
    seed = jnp.uint32(11)
    best_k, prop_k = lm_ops.local_move_plp(
        r_ids, nbr, w, labels_ext, seed, use_pallas=True, interpret=True,
        **kw)
    best_r, prop_r = lm_ops.local_move_plp(
        r_ids, nbr, w, labels_ext, seed, use_pallas=False, **kw)
    np.testing.assert_array_equal(np.asarray(best_k), np.asarray(best_r))
    np.testing.assert_array_equal(np.asarray(prop_k), np.asarray(prop_r))


@pytest.mark.parametrize("width", BUCKET_WIDTHS)
@pytest.mark.parametrize("singleton_rule", [True, False])
def test_louvain_gathered_layout_matches_ref(width, singleton_rule,
                                             tpu_layout):
    rows = 8 if width >= 256 else 32
    n = 64
    r_ids, nbr, w, _, tables = _tiles(rows, width, n, seed=width + 3)
    kw = dict(sentinel=n, singleton_rule=singleton_rule)
    vol_total = jnp.float32(41.0)
    best_k, prop_k = lm_ops.local_move_louvain(
        r_ids, nbr, w, vol_total=vol_total, use_pallas=True, interpret=True,
        **tables, **kw)
    best_r, prop_r = lm_ops.local_move_louvain(
        r_ids, nbr, w, vol_total=vol_total, use_pallas=False, **tables, **kw)
    np.testing.assert_array_equal(np.asarray(best_k), np.asarray(best_r))
    np.testing.assert_array_equal(np.asarray(prop_k), np.asarray(prop_r))


def test_fused_matches_legacy_two_step():
    """The fused kernel must reproduce the legacy gather-outside two-step
    (jnp gathers into (rows, W) tiles + label_argmax / delta_q kernels)
    bit-for-bit — the contract the gather_fusion benchmark relies on."""
    n = 96
    r_ids, nbr, w, labels_ext, tables = _tiles(48, 16, n, seed=5)
    seed = jnp.uint32(3)

    # PLP
    best_f, prop_f = lm_ops.local_move_plp(
        r_ids, nbr, w, labels_ext, seed, tie_eps=0.25, sentinel=n,
        use_pallas=True)
    nbr_lab = jnp.where(nbr < n, labels_ext[jnp.clip(nbr, 0, n)], n)
    cur_lab = labels_ext[jnp.clip(r_ids, 0, n)]
    best_l, bs, cs = la_ops.label_argmax(
        nbr_lab, w, cur_lab, jnp.where(r_ids < n, r_ids, n), seed,
        tie_eps=0.25, sentinel=n, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(best_f), np.asarray(best_l))
    np.testing.assert_array_equal(
        np.asarray(prop_f), np.asarray((best_l >= 0) & (bs > cs)))

    # Louvain
    vol_total = jnp.float32(41.0)
    best_f, prop_f = lm_ops.local_move_louvain(
        r_ids, nbr, w, vol_total=vol_total, sentinel=n, singleton_rule=True,
        use_pallas=True, **tables)
    com_ext, vol_ext = tables["com_ext"], tables["vol_ext"]
    size_ext, deg_ext = tables["size_ext"], tables["deg_ext"]
    rows_c = jnp.clip(r_ids, 0, n)
    cand = jnp.where(nbr < n, com_ext[jnp.clip(nbr, 0, n)], n)
    best_l, gain = dq_ops.delta_q_argmax(
        cand_com=cand, nbr_w=w, cur_com=com_ext[rows_c],
        deg_v=deg_ext[rows_c],
        vol_cand=vol_ext[jnp.clip(cand, 0, n)],
        vol_cur=vol_ext[jnp.clip(com_ext[rows_c], 0, n)],
        size_cand=size_ext[jnp.clip(cand, 0, n)],
        size_cur=size_ext[jnp.clip(com_ext[rows_c], 0, n)],
        vol_total=vol_total, sentinel=n, singleton_rule=True,
        use_pallas=True)
    np.testing.assert_array_equal(np.asarray(best_f), np.asarray(best_l))
    np.testing.assert_array_equal(
        np.asarray(prop_f), np.asarray((best_l >= 0) & (gain > 0.0)))


def test_chunk_stacked_input_shapes():
    """ops must accept the (n_chunks, rows) stacked DeviceBucket layout and
    agree with the flattened grid_view call."""
    g = _graph(seed=2, n=120, k=4)
    n = g.n_max
    ell = to_device(g, build_ell(g, widths=(8, 16)), rows_per_chunk=8)
    labels_ext = jnp.concatenate(
        [jnp.arange(n, dtype=jnp.int32), jnp.int32([n])])
    b = ell.buckets[0]
    assert b.rows.ndim == 2 and b.rows.shape[0] > 1  # really chunk-stacked
    best_s, prop_s = lm_ops.local_move_plp(
        b.rows, b.nbr, b.w, labels_ext, jnp.uint32(0),
        tie_eps=0.25, sentinel=n, use_pallas=True)
    rows, nbr, w = grid_view(b)
    best_f, prop_f = lm_ops.local_move_plp(
        rows, nbr, w, labels_ext, jnp.uint32(0),
        tie_eps=0.25, sentinel=n, use_pallas=True)
    assert best_s.shape == b.rows.shape
    np.testing.assert_array_equal(
        np.asarray(best_s).ravel(), np.asarray(best_f))
    np.testing.assert_array_equal(
        np.asarray(prop_s).ravel(), np.asarray(prop_f))


@pytest.mark.parametrize("evaluator", ["plp", "louvain"])
def test_sweep_backends_bitwise_equal(evaluator):
    """Full fused phase: pallas (fused kernel) ≡ ell (jnp ref) ≡ segment
    evaluator, labels and histories bit-for-bit."""
    g = _graph()
    res = {}
    for backend in ("segment", "ell", "pallas"):
        spec = EngineSpec(evaluator=evaluator, backend=backend,
                          max_sweeps=30, move_prob=0.75)
        eng = SweepEngine(g, spec)
        res[backend] = eng.run_phase(*eng.singleton_state(), seed=3)
    for backend in ("ell", "pallas"):
        np.testing.assert_array_equal(
            np.asarray(res["segment"].labels), np.asarray(res[backend].labels))
        assert res[backend].sweeps == res["segment"].sweeps
        assert (res[backend].delta_n_history
                == res["segment"].delta_n_history)


@pytest.mark.parametrize("evaluator", ["plp", "louvain"])
def test_sweep_tail_heavy_bitwise_equal(evaluator):
    """Tiny bucket widths force most vertices onto the tail path; pallas and
    ell must still agree bit-for-bit with each other."""
    g = _graph(seed=11)
    ell = to_device(g, build_ell(g, widths=(4, 8)))
    assert ell.has_tail
    res = {}
    for backend in ("ell", "pallas"):
        spec = EngineSpec(evaluator=evaluator, backend=backend,
                          max_sweeps=30, move_prob=0.75)
        eng = SweepEngine(g, spec, ell=ell)
        res[backend] = eng.run_phase(*eng.singleton_state(), seed=5)
    np.testing.assert_array_equal(
        np.asarray(res["ell"].labels), np.asarray(res["pallas"].labels))
    assert res["ell"].delta_n_history == res["pallas"].delta_n_history


@pytest.mark.parametrize("evaluator", ["plp", "louvain"])
def test_sweep_gathered_matches_segment(evaluator, tpu_layout):
    """The gathered layout (the pallas backend's only layout on TPU) over a
    whole fused phase ≡ the segment evaluator, labels and histories."""
    g = _graph(seed=17)
    res = {}
    for backend in ("segment", "pallas"):
        spec = EngineSpec(evaluator=evaluator, backend=backend,
                          max_sweeps=30, move_prob=0.75)
        eng = SweepEngine(g, spec)
        res[backend] = eng.run_phase(*eng.singleton_state(), seed=9)
    np.testing.assert_array_equal(
        np.asarray(res["segment"].labels), np.asarray(res["pallas"].labels))
    assert res["pallas"].delta_n_history == res["segment"].delta_n_history


# ------------------------------------------------------- windowed streaming


def _windows_for(r_ids, nbr, n, block_rows):
    return compute_windows(np.asarray(r_ids), np.asarray(nbr), n, block_rows)


@pytest.mark.parametrize("width", BUCKET_WIDTHS)
@pytest.mark.parametrize("block_rows", [8, 64])
def test_plp_streamed_matches_resident(width, block_rows):
    """Streamed kernel ≡ streamed jnp oracle ≡ resident ref, bit-for-bit.

    block_rows=64 exceeds the tile row count → degenerate 1-block grid; the
    random (non-locality-ordered) tiles make every block span nearly the
    whole id range, so the window also degenerates to whole-table coverage
    (slot ≥ n+1) — both documented edge cases."""
    rows = 8 if width >= 256 else 32
    n = 64
    r_ids, nbr, w, labels_ext, _ = _tiles(rows, width, n, seed=width + 2)
    win = _windows_for(r_ids, nbr, n, block_rows)
    if block_rows == 64:
        assert win.win_blk.shape[0] == 1          # degenerate 1-block grid
    assert win.slot >= n + 1                      # window spans whole table
    kw = dict(tie_eps=0.25, sentinel=n)
    seed = jnp.uint32(7)
    best_r, prop_r = lm_ops.local_move_plp(
        r_ids, nbr, w, labels_ext, seed, table_mode="resident", **kw)
    best_k, prop_k = lm_ops.local_move_plp(
        r_ids, nbr, w, labels_ext, seed, use_pallas=True, interpret=True,
        windows=win, table_mode="streamed", **kw)
    best_j, prop_j = lm_ops.local_move_plp(
        r_ids, nbr, w, labels_ext, seed,
        windows=win, table_mode="streamed", **kw)
    for got in ((best_k, prop_k), (best_j, prop_j)):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(best_r))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(prop_r))


@pytest.mark.parametrize("width", BUCKET_WIDTHS)
@pytest.mark.parametrize("singleton_rule", [True, False])
def test_louvain_streamed_matches_resident(width, singleton_rule):
    rows = 8 if width >= 256 else 32
    n = 64
    r_ids, nbr, w, _, tables = _tiles(rows, width, n, seed=width + 3)
    win = _windows_for(r_ids, nbr, n, block_rows=8)
    kw = dict(sentinel=n, singleton_rule=singleton_rule)
    vol_total = jnp.float32(37.0)
    best_r, prop_r = lm_ops.local_move_louvain(
        r_ids, nbr, w, vol_total=vol_total, table_mode="resident",
        **tables, **kw)
    best_k, prop_k = lm_ops.local_move_louvain(
        r_ids, nbr, w, vol_total=vol_total, use_pallas=True, interpret=True,
        windows=win, table_mode="streamed", **tables, **kw)
    best_j, prop_j = lm_ops.local_move_louvain(
        r_ids, nbr, w, vol_total=vol_total,
        windows=win, table_mode="streamed", **tables, **kw)
    for got in ((best_k, prop_k), (best_j, prop_j)):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(best_r))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(prop_r))


def _banded_graph(n=1024, band=40, k=6, seed=3):
    """Random graph whose neighbors sit within ``band`` ids of the vertex —
    the id-locality the streamed path exploits (Sahu, arXiv:2301.12390)."""
    rng = np.random.default_rng(seed)
    u = np.repeat(np.arange(n), k)
    v = np.clip(u + rng.integers(1, band, size=n * k), 0, n - 1)
    keep = u != v
    u, v = u[keep], v[keep]
    uu, vv = np.concatenate([u, v]), np.concatenate([v, u])
    return from_numpy_edges(uu, vv, np.ones(uu.size, np.float32))


def test_streamed_narrow_windows_on_real_buckets():
    """Locality-ordered banded buckets must yield windows NARROWER than the
    table (the whole point of streaming) and still score bit-identically."""
    g = _banded_graph()
    n = g.n_max
    ell = to_device(g, build_ell(g, widths=(16, 64)), block_rows=64)
    labels_ext = jnp.concatenate(
        [jnp.arange(n, dtype=jnp.int32) % 97, jnp.int32([n])])
    narrow = 0
    for b in ell.buckets:
        if b.n_rows_valid == 0:
            continue
        assert b.windows is not None
        narrow += int(b.windows.slot < n + 1)
        rows, nbr, w = grid_view(b)
        best_r, prop_r = lm_ops.local_move_plp(
            rows, nbr, w, labels_ext, jnp.uint32(3),
            tie_eps=0.25, sentinel=n, table_mode="resident")
        best_s, prop_s = lm_ops.local_move_plp(
            rows, nbr, w, labels_ext, jnp.uint32(3),
            tie_eps=0.25, sentinel=n, use_pallas=True, interpret=True,
            windows=b.windows, table_mode="streamed")
        np.testing.assert_array_equal(np.asarray(best_s), np.asarray(best_r))
        np.testing.assert_array_equal(np.asarray(prop_s), np.asarray(prop_r))
    assert narrow > 0, "no bucket produced a sub-table window"


def test_streamed_requires_windows():
    n = 32
    r_ids, nbr, w, labels_ext, _ = _tiles(16, 16, n, seed=1)
    with pytest.raises(ValueError, match="window metadata"):
        lm_ops.local_move_plp(
            r_ids, nbr, w, labels_ext, jnp.uint32(0),
            tie_eps=0.25, sentinel=n, table_mode="streamed")


def test_auto_mode_rejects_degenerate_windows():
    """Windows as wide as the table make streaming strictly worse than the
    resident one-shot DMA: auto falls back to resident even past the byte
    threshold; explicit 'streamed' is still honored (parity tests use it)."""
    from repro.kernels.local_move.ops import _resolve_mode

    n = 64  # n_pad = 128; random tiles span the whole range -> 2*slot >= n_pad
    r_ids, nbr, w, _, _ = _tiles(16, 16, n, seed=2)
    win = _windows_for(r_ids, nbr, n, 8)
    assert 2 * win.slot >= 128
    assert _resolve_mode("auto", win, 1, n, 1) == "resident"
    assert _resolve_mode("streamed", win, 1, n, 1) == "streamed"


def test_table_mode_auto_flips_at_budget_threshold():
    """The documented rule: resident iff table_bytes <= budget // 2, with
    the budget taken from kwarg > env var > default, in that order."""
    assert kcommon.resolve_table_mode("auto", 512, budget_bytes=1024) == "resident"
    assert kcommon.resolve_table_mode("auto", 513, budget_bytes=1024) == "streamed"
    assert kcommon.resolve_table_mode("resident", 1 << 40) == "resident"
    assert kcommon.resolve_table_mode("streamed", 1) == "streamed"
    with pytest.raises(ValueError, match="table_mode"):
        kcommon.resolve_table_mode("bogus", 1)
    old = os.environ.get(kcommon.VMEM_BUDGET_ENV)
    os.environ[kcommon.VMEM_BUDGET_ENV] = "2048"
    try:
        assert kcommon.resolve_table_mode("auto", 1024) == "resident"
        assert kcommon.resolve_table_mode("auto", 1025) == "streamed"
        # explicit kwarg outranks the env var
        assert kcommon.resolve_table_mode("auto", 1025, budget_bytes=1 << 20) \
            == "resident"
    finally:
        if old is None:
            del os.environ[kcommon.VMEM_BUDGET_ENV]
        else:
            os.environ[kcommon.VMEM_BUDGET_ENV] = old
    # pick_row_block_fused charges the table bytes: a fatter table must
    # shrink (never grow) the row block
    assert (kcommon.pick_row_block_fused(64, table_bytes=6 << 20)
            < kcommon.pick_row_block_fused(64, table_bytes=0))
    assert kcommon.pick_row_block_fused(16, table_bytes=0) == 2048
    # tables alone past half the budget: the pairwise floor (budget//8)
    # keeps a sane grid instead of collapsing to 1-row steps — that regime
    # cannot fit VMEM via row-block shrinking anyway (streamed-or-bust)
    assert kcommon.pick_row_block_fused(64, table_bytes=1 << 30) == \
        kcommon.pick_row_block_fused(
            64, budget_bytes=kcommon.vmem_budget_bytes() // 4)


@pytest.mark.parametrize("evaluator", ["plp", "louvain"])
def test_sweep_streamed_backends_bitwise_equal(evaluator):
    """Full fused phase with table_mode='streamed': pallas (streamed kernel)
    ≡ ell (windowed jnp oracle) ≡ the resident segment evaluator."""
    g = _graph()
    res = {}
    for backend, tm in (("segment", "auto"), ("ell", "streamed"),
                        ("pallas", "streamed")):
        spec = EngineSpec(evaluator=evaluator, backend=backend,
                          max_sweeps=30, move_prob=0.75, table_mode=tm)
        eng = SweepEngine(g, spec)
        res[backend] = eng.run_phase(*eng.singleton_state(), seed=3)
    for backend in ("ell", "pallas"):
        np.testing.assert_array_equal(
            np.asarray(res["segment"].labels), np.asarray(res[backend].labels))
        assert res[backend].sweeps == res["segment"].sweeps
        assert (res[backend].delta_n_history
                == res["segment"].delta_n_history)


@pytest.mark.parametrize("evaluator", ["plp", "louvain"])
def test_sweep_tail_heavy_matches_segment(evaluator):
    """Tail-heavy layout vs the segment evaluator: locks the tail path's
    once-per-sweep extended-table gathers (moves.*_tables) AND the streamed
    bucket path to the segment reference bit-for-bit."""
    g = _graph(seed=11)
    ell = to_device(g, build_ell(g, widths=(4, 8)))
    assert ell.has_tail
    seg = SweepEngine(g, EngineSpec(evaluator=evaluator, backend="segment",
                                    max_sweeps=30, move_prob=0.75))
    base = seg.run_phase(*seg.singleton_state(), seed=5)
    for backend in ("ell", "pallas"):
        for tm in ("resident", "streamed"):
            spec = EngineSpec(evaluator=evaluator, backend=backend,
                              max_sweeps=30, move_prob=0.75, table_mode=tm)
            eng = SweepEngine(g, spec, ell=ell)
            r = eng.run_phase(*eng.singleton_state(), seed=5)
            np.testing.assert_array_equal(
                np.asarray(base.labels), np.asarray(r.labels))
            assert base.delta_n_history == r.delta_n_history


def _streaming_budget(windows, n_tables, n_max):
    """A VMEM budget under which auto genuinely streams: the double-buffered
    windows fit half of it, the resident tables do not (ops._resolve_mode)."""
    win_bytes = 4 * n_tables * (2 * windows.slot) * 2
    n_pad = -(-(n_max + 1) // 128) * 128
    table_bytes = 4 * n_tables * n_pad
    assert win_bytes < table_bytes, "windows too coarse to ever win"
    return 2 * ((win_bytes + table_bytes) // 2)


def test_e2e_streamed_past_resident_budget():
    """A graph whose tables exceed the (shrunk) resident VMEM budget must
    run end-to-end through louvain()/plp() on the pallas backend via the
    auto-selected streamed path, bit-identical to the resident run.

    The graph must have genuine id-locality (banded) and enough rows per
    bucket for multi-block grids — otherwise auto correctly refuses
    windows that are no cheaper than the resident tables and stays
    resident."""
    from repro.core.louvain import LouvainConfig, louvain
    from repro.core.plp import PLPConfig, plp
    from repro.graph.ell import build_device_ell
    from repro.kernels.local_move.ops import _resolve_mode

    g = _banded_graph(n=16384, band=48, k=3, seed=5)
    n = g.n_max
    # per-evaluator budgets derived from the ACTUAL default-build windows,
    # with an engagement assertion so slot drift fails loudly here rather
    # than silently degrading to a resident-only test
    ell = build_device_ell(g)
    big = max((b for b in ell.buckets if b.n_rows_valid),
              key=lambda b: b.n_rows_valid)
    budget = {nt: _streaming_budget(big.windows, nt, n) for nt in (1, 4)}
    assert _resolve_mode("auto", big.windows, 1, n, budget[1]) == "streamed"
    assert _resolve_mode("auto", big.windows, 4, n, budget[4]) == "streamed"

    lcfg = LouvainConfig(seed=8, backend="pallas", track_modularity=False)
    pcfg = PLPConfig(seed=8, backend="pallas")
    r_res = louvain(g, lcfg.replace(table_mode="resident"))
    p_res = plp(g, pcfg.replace(table_mode="resident"))
    old = os.environ.get(kcommon.VMEM_BUDGET_ENV)
    try:
        os.environ[kcommon.VMEM_BUDGET_ENV] = str(budget[4])
        r_str = louvain(g, lcfg)
        os.environ[kcommon.VMEM_BUDGET_ENV] = str(budget[1])
        p_str = plp(g, pcfg)
    finally:
        if old is None:
            del os.environ[kcommon.VMEM_BUDGET_ENV]
        else:
            os.environ[kcommon.VMEM_BUDGET_ENV] = old
    np.testing.assert_array_equal(r_res.labels, r_str.labels)
    assert r_res.levels == r_str.levels
    assert r_res.sweeps_per_level == r_str.sweeps_per_level
    np.testing.assert_array_equal(p_res.labels, p_str.labels)
    assert p_res.iterations == p_str.iterations


def test_pipeline_pallas_matches_ell_end_to_end():
    """Fused multi-level pipeline: the pallas backend (level 0 through the
    fused kernel) must reproduce the ell backend's whole-run result."""
    from repro.core.louvain import LouvainConfig, louvain

    g = _graph(seed=4)
    cfg = LouvainConfig(seed=4, track_modularity=False, pipeline_fused=True)
    r_ell = louvain(g, cfg.replace(backend="ell"))
    r_pal = louvain(g, cfg.replace(backend="pallas"))
    np.testing.assert_array_equal(
        np.asarray(r_ell.labels), np.asarray(r_pal.labels))
    assert r_ell.levels == r_pal.levels
    assert r_ell.sweeps_per_level == r_pal.sweeps_per_level
    assert r_ell.modularity == r_pal.modularity
