"""§Perf variant runner: lower a cell under config overrides and report the
three roofline terms — the measurement half of the hypothesis loop.

  PYTHONPATH=src python -m benchmarks.perf_variants qwen3-8b decode_32k \
      kv_cache_dtype=int8 serve_bf16=1

Community-detection sweep mode (DESIGN.md §Engine): time the fused
while_loop phase against the stepwise per-sweep-dispatch reference —

  PYTHONPATH=src python -m benchmarks.perf_variants community com-dblp \
      algo=plp repeat=3

Level-fusion mode (DESIGN.md §Pipeline): time the whole-run fused pipeline
(one dispatch per louvain() call) against the per-level driver, with the
paper-style fig4 local-moving/aggregation phase split per level and the
one-sort vs two-sort groupby compaction delta —

  PYTHONPATH=src python -m benchmarks.perf_variants level_fusion com-dblp \
      algo=both repeat=3

Gather-fusion mode (DESIGN.md §Kernels): time the fused gather-in-kernel
local_move kernel against the legacy two-step path (HBM-gathered tiles +
label_argmax/delta_q kernel, with and without the old per-bucket lax.scan
chunk chain), per bucket width, checking bit-identical outputs —

  PYTHONPATH=src python -m benchmarks.perf_variants gather_fusion com-dblp \
      algo=both repeat=3

Table-streaming mode (DESIGN.md §Kernels): time the windowed streamed table
layout against the VMEM-resident fast path (and the legacy two-step), per
bucket width, with per-bucket window stats and a bit-identical check —

  PYTHONPATH=src python -m benchmarks.perf_variants table_streaming com-dblp \
      algo=both repeat=3 block_rows=512

Coarse-cascade mode (DESIGN.md §Pipeline): time the capacity-scheduled
cascade against the fixed-capacity pipeline and the per-level driver, with
the paper-style Fig. 4 level-0 vs aggregation+coarse-tail split, the number
of compiled stage programs, and a bit-identical check —

  PYTHONPATH=src python -m benchmarks.perf_variants coarse_cascade \
      com-amazon algo=louvain repeat=3 backend=ell

Aggregation mode (DESIGN.md §Aggregation kernel): time the sort-free binned
coarsening against the one-sort fused oracle and the legacy two-step
(remap sort + groupby sort) on every level's ACTUAL aggregation inputs,
replayed at the cascade stage capacity each level runs under, with a
bit-identical check per level and the Fig. 4-style per-level local-moving /
aggregation split for both paths —

  PYTHONPATH=src python -m benchmarks.perf_variants aggregation \
      com-amazon algo=louvain repeat=3

Batch-serve mode (DESIGN.md §Serving): throughput and latency of the
capacity-bucketed batched engine (``louvain_batch``/``plp_batch``) against
a sequential single-graph loop over the same many-small-graph workload
(ego-net stand-ins), with a per-graph bitwise parity check against the
unbatched oracle and a zero-recompile assertion on the steady state —

  PYTHONPATH=src python -m benchmarks.perf_variants batch_serve \
      com-dblp algo=both repeat=3 n_graphs=64
"""
import json
import os
import sys

import jax
import jax.numpy as jnp


def run(arch: str, shape: str, overrides: dict, serve_bf16: bool = False):
    # The production-mesh lowering needs 512 fake host devices; set the flag
    # here (before first backend init) rather than at import so that
    # `community` mode — which measures single-device dispatch overhead —
    # runs under the normal runtime.
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    from repro import configs
    from repro.models import api as model_api
    from repro.models.arch_config import SHAPES
    from repro.launch import sharding as shd
    from repro.launch import hlo_cost
    from repro.launch.mesh import make_production_mesh
    from repro.launch.train_step import (make_decode_step, make_prefill_step,
                                         make_train_step)
    from repro.launch.dryrun import _opt_state_specs
    from repro.models.api import to_shape_tree
    from repro.train import optim

    c = configs.get(arch)
    if overrides:
        c = c.replace(**overrides)
    cell = SHAPES[shape]
    model = model_api.build(c)
    mesh = make_production_mesh(multi_pod=False)
    rules = {"embed_act": "model"} if c.shard_residual_embed else {}
    with shd.use_mesh(mesh, rules):
        pspecs = to_shape_tree(model.decls)
        if serve_bf16:
            # serving deployments store bf16 weights (no optimizer on box)
            pspecs = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                if s.dtype == jnp.float32 and len(s.shape) >= 2 else s, pspecs)
        if cell.kind == "train":
            opt_cfg = optim.OptimConfig(name=c.optimizer)
            step, in_sh, out_sh, _ = make_train_step(model, opt_cfg, cell, mesh)
            lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=(0, 1)).lower(
                pspecs, _opt_state_specs(c, model, pspecs),
                model.input_specs(cell))
        elif cell.kind == "prefill":
            step, in_sh, out_sh = make_prefill_step(model, cell, mesh)
            lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh
                              ).lower(pspecs, model.input_specs(cell))
        else:
            step, in_sh, out_sh = make_decode_step(model, cell, mesh)
            lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=(2,)).lower(
                pspecs, model.input_specs(cell)["token"],
                model.decode_state_specs(cell))
        compiled = lowered.compile()
    a = hlo_cost.analyze(compiled.as_text())
    out = {
        "arch": arch, "shape": shape, "overrides": overrides,
        "serve_bf16": serve_bf16,
        "compute_s": a["flops_per_device"] / 197e12,
        "memory_s": a["bytes_per_device"] / 819e9,
        "collective_s": a["collective_bytes_per_device"] / 50e9,
    }
    print(json.dumps(out, indent=1))
    return out


def run_community(dataset: str = "com-dblp", algo: str = "both",
                  repeat: int = 3, backend: str = "segment"):
    """Fused vs stepwise sweep timings for the community-detection engine.

    ``fused`` runs each local-moving phase as one jitted lax.while_loop call;
    ``stepwise`` dispatches one jitted call + one ΔN host sync per sweep.
    Labels are bit-identical between the two (tests/test_engine.py); the
    delta is pure dispatch/transfer overhead.
    """
    import time

    from repro.core.louvain import LouvainConfig, louvain
    from repro.core.plp import PLPConfig, plp
    from repro.graph import datasets

    lg = datasets.load(dataset)
    g = lg.graph
    out = {"mode": "community", "dataset": dataset, "V": lg.n,
           "E": lg.m_undirected, "backend": backend}

    def best_of(fn):
        fn()  # warm: compile both paths before timing
        t_best = None
        for _ in range(repeat):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            t_best = dt if t_best is None else min(t_best, dt)
        return t_best

    if algo in ("plp", "both"):
        cfg = PLPConfig(max_iterations=60, backend=backend)
        out["plp_fused_s"] = best_of(lambda: plp(g, cfg.replace(fused=True)))
        out["plp_stepwise_s"] = best_of(lambda: plp(g, cfg.replace(fused=False)))
        out["plp_fused_speedup"] = out["plp_stepwise_s"] / out["plp_fused_s"]
    if algo in ("louvain", "both"):
        # pipeline_fused pinned False: this mode isolates the §Engine
        # per-SWEEP dispatch overhead; §Pipeline level-loop fusion is
        # measured separately by run_level_fusion
        cfg = LouvainConfig(track_modularity=False, backend=backend,
                            pipeline_fused=False)
        out["louvain_fused_s"] = best_of(
            lambda: louvain(g, cfg.replace(fused=True)))
        out["louvain_stepwise_s"] = best_of(
            lambda: louvain(g, cfg.replace(fused=False)))
        out["louvain_fused_speedup"] = (
            out["louvain_stepwise_s"] / out["louvain_fused_s"])
    print(json.dumps(out, indent=1))
    return out


def run_level_fusion(dataset: str = "com-dblp", algo: str = "both",
                     repeat: int = 3, backend: str = "segment"):
    """Whole-run pipeline fusion vs per-level driver (DESIGN.md §Pipeline).

    ``pipeline_fused=True`` runs the entire level loop (local-moving +
    aggregation + modularity accounting) as ONE jitted lax.while_loop with
    one readback; ``pipeline_fused=False`` dispatches one fused local-moving
    phase per level and aggregates on host.  Results are bit-identical
    (tests/test_pipeline.py); the delta is per-level dispatch + transfer
    overhead.  Also reports:

      * the paper-style fig4 phase split per level (local-moving vs
        aggregation wall share, from the per-level driver's level-tagged
        timer) plus the on-device histories of the fused run (sweeps, ΔN,
        community counts per level);
      * the aggregation compaction delta: one-sort scatter vs legacy
        two-sort argsort ``groupby_sum`` on this dataset's coarsening keys.
    """
    import time

    import numpy as np

    from repro.core.louvain import LouvainConfig, louvain, leiden
    from repro.graph import datasets
    from repro.graph import segment as seg

    lg = datasets.load(dataset)
    g = lg.graph
    out = {"mode": "level_fusion", "dataset": dataset, "V": lg.n,
           "E": lg.m_undirected, "backend": backend}

    def ab_best(fa, fb):
        """Interleaved A/B best-of timing: warm both once, then alternate
        repeats so CPU frequency / cache drift biases neither side (results
        are deterministic; the warm run's result is returned)."""
        warm = fa()
        fb()
        ta = tb = None
        for _ in range(repeat):
            t0 = time.perf_counter()
            fa()
            dt = time.perf_counter() - t0
            ta = dt if ta is None else min(ta, dt)
            t0 = time.perf_counter()
            fb()
            dt = time.perf_counter() - t0
            tb = dt if tb is None else min(tb, dt)
        return ta, tb, warm

    algos = ("louvain", "leiden") if algo == "both" else (algo,)
    for name in algos:
        run = leiden if name == "leiden" else louvain
        cfg = LouvainConfig(track_modularity=False, backend=backend)
        (out[f"{name}_pipeline_s"], out[f"{name}_per_level_s"],
         res) = ab_best(
            lambda: run(g, cfg.replace(pipeline_fused=True)),
            lambda: run(g, cfg.replace(pipeline_fused=False)))
        out[f"{name}_pipeline_speedup"] = (
            out[f"{name}_per_level_s"] / out[f"{name}_pipeline_s"])

        # on-device histories from the (deterministic) fused warm run
        out[f"{name}_levels"] = res.levels
        out[f"{name}_sweeps_per_level"] = res.sweeps_per_level
        out[f"{name}_n_comm_per_level"] = res.n_comm_per_level
        out[f"{name}_delta_n_per_level"] = res.delta_n_per_level

        # fig4-style per-level phase split from the per-level driver
        res_t = run(g, cfg.replace(pipeline_fused=False,
                                   per_level_timing=True))
        split = []
        for level in range(res_t.levels):
            lm = res_t.timer.totals.get(f"L{level:02d}/local_moving", 0.0)
            ag = res_t.timer.totals.get(f"L{level:02d}/aggregation", 0.0)
            rf = res_t.timer.totals.get(f"L{level:02d}/refinement", 0.0)
            tot = lm + ag or 1e-12
            split.append({"level": level, "local_moving_s": lm,
                          "aggregation_s": ag, "refinement_s": rf,
                          "aggregation_share": ag / tot})
        out[f"{name}_phase_split"] = split

    # groupby compaction micro-benchmark on this graph's level-0 coarsening
    # keys: one lax.sort (scatter compaction) vs two (argsort compaction)
    import jax
    import jax.numpy as jnp

    res0 = louvain(g, LouvainConfig(track_modularity=False, max_levels=1,
                                    backend=backend))
    com = jnp.asarray(
        np.concatenate([res0.labels,
                        np.arange(len(res0.labels), g.n_max)]), jnp.int32)
    n = g.n_max
    csrc = jnp.where(g.edge_mask, com[jnp.clip(g.src, 0, n - 1)], n)
    cdst = jnp.where(g.edge_mask, com[jnp.clip(g.dst, 0, n - 1)], n)
    w = jnp.where(g.edge_mask, g.w, 0.0)
    fns = {how: jax.jit(lambda a, b, v, m, how=how: seg.groupby_sum(
        (a, b), v, valid=m, compact_via=how)[1]) for how in
        ("scatter", "argsort")}
    (out["groupby_scatter_s"], out["groupby_argsort_s"], _) = ab_best(
        lambda: jax.block_until_ready(
            fns["scatter"](csrc, cdst, w, g.edge_mask)),
        lambda: jax.block_until_ready(
            fns["argsort"](csrc, cdst, w, g.edge_mask)))
    out["groupby_scatter_speedup"] = (
        out["groupby_argsort_s"] / out["groupby_scatter_s"])

    print(json.dumps(out, indent=1))
    return out


def run_gather_fusion(dataset: str = "com-dblp", algo: str = "both",
                      repeat: int = 3):
    """Fused gather-in-kernel local_move vs the legacy two-step path
    (DESIGN.md §Kernels), per bucket width.

    Three variants per degree bucket, all through the Pallas kernels:

      * ``fused``       — ONE local_move grid call: tables ride along whole,
                          gathers happen in-kernel, grid spans all chunks.
      * ``two_step``    — the gathered (rows, W) label/vol/size/deg tiles are
                          materialized outside, then label_argmax / delta_q
                          scores them (no scan — isolates the gather traffic).
      * ``legacy_scan`` — two_step driven through the pre-refactor per-bucket
                          lax.scan chunk chain (the exact old engine path).

    Outputs are checked bit-identical between fused and both baselines.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import moves
    from repro.graph import datasets
    from repro.graph.ell import build_device_ell, grid_view
    from repro.kernels.delta_q import ops as dq_ops
    from repro.kernels.label_argmax import ops as la_ops
    from repro.kernels.local_move import ops as lm_ops

    lg = datasets.load(dataset)
    g = lg.graph
    n = g.n_max
    ell = build_device_ell(g)
    out = {"mode": "gather_fusion", "dataset": dataset, "V": lg.n,
           "E": lg.m_undirected}

    # per-sweep state at singleton init — the tables every variant consumes
    labels = jnp.arange(n, dtype=jnp.int32)
    labels_ext = jnp.concatenate([labels, jnp.int32([n])])
    vmask = g.vertex_mask()
    deg = g.weighted_degrees()
    vol_v = g.total_volume()
    vol_com, size_com = moves.community_aux(labels, deg, vmask, n)
    com_ext = labels_ext
    vol_ext = jnp.concatenate([vol_com, jnp.zeros((1,), vol_com.dtype)])
    size_ext = jnp.concatenate([size_com, jnp.zeros((1,), size_com.dtype)])
    deg_ext = jnp.concatenate([deg, jnp.zeros((1,), deg.dtype)])
    seed = jnp.uint32(0)

    def best_of(fn):
        res = jax.block_until_ready(fn())  # warm/compile
        t_best = None
        for _ in range(repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            dt = time.perf_counter() - t0
            t_best = dt if t_best is None else min(t_best, dt)
        return t_best, res

    def plp_two_step(r_, nb, w_):
        nbr_lab = jnp.where(nb < n, labels_ext[jnp.clip(nb, 0, n)], n)
        cur_lab = labels_ext[jnp.clip(r_, 0, n)]
        best, bs, cs = la_ops.label_argmax(
            nbr_lab, w_, cur_lab, jnp.where(r_ < n, r_, n), seed,
            tie_eps=0.25, sentinel=n, use_pallas=True)
        return best, (best >= 0) & (bs > cs)

    def louvain_two_step(r_, nb, w_):
        rows_c = jnp.clip(r_, 0, n)
        cand = jnp.where(nb < n, com_ext[jnp.clip(nb, 0, n)], n)
        best, gain = dq_ops.delta_q_argmax(
            cand_com=cand, nbr_w=w_, cur_com=com_ext[rows_c],
            deg_v=deg_ext[rows_c],
            vol_cand=vol_ext[jnp.clip(cand, 0, n)],
            vol_cur=vol_ext[jnp.clip(com_ext[rows_c], 0, n)],
            size_cand=size_ext[jnp.clip(cand, 0, n)],
            size_cur=size_ext[jnp.clip(com_ext[rows_c], 0, n)],
            vol_total=vol_v, sentinel=n, singleton_rule=True,
            use_pallas=True)
        return best, (best >= 0) & (gain > 0.0)

    algos = ("plp", "louvain") if algo == "both" else (algo,)
    for name in algos:
        two = plp_two_step if name == "plp" else louvain_two_step
        if name == "plp":
            def fused(r_, nb, w_):
                return lm_ops.local_move_plp(
                    r_, nb, w_, labels_ext, seed, tie_eps=0.25, sentinel=n,
                    use_pallas=True)
        else:
            def fused(r_, nb, w_):
                return lm_ops.local_move_louvain(
                    r_, nb, w_, com_ext, vol_ext, size_ext, deg_ext, vol_v,
                    sentinel=n, singleton_rule=True, use_pallas=True)

        def legacy_scan(rows_s, nbr_s, w_s):
            def chunk(carry, c):
                best, good = two(*c)
                return carry, (best, good)
            _, o = jax.lax.scan(chunk, 0, (rows_s, nbr_s, w_s))
            return o[0].reshape(-1), o[1].reshape(-1)

        fused_j = jax.jit(fused)
        two_j = jax.jit(two)
        legacy_j = jax.jit(legacy_scan)

        widths = []
        tot = {"fused_s": 0.0, "two_step_s": 0.0, "legacy_scan_s": 0.0}
        identical = True
        for b in ell.buckets:
            rows, nbr, w = grid_view(b)
            # the old engine evaluated every bucket; the fused engine skips
            # statically-empty ones at trace time (graph/ell.DeviceBucket)
            t_t, r_t = best_of(lambda: two_j(rows, nbr, w))
            t_l, r_l = best_of(lambda: legacy_j(b.rows, b.nbr, b.w))
            if b.n_rows_valid == 0:
                rec = {"width": b.width, "rows": int(rows.shape[0]),
                       "rows_real": 0, "chunks": int(b.rows.shape[0]),
                       "fused_s": 0.0, "two_step_s": t_t,
                       "legacy_scan_s": t_l, "statically_skipped": True,
                       "bit_identical": True}
            else:
                t_f, r_f = best_of(lambda: fused_j(rows, nbr, w))
                same = all(
                    bool(jnp.array_equal(a, c)) and bool(jnp.array_equal(a, d))
                    for a, c, d in zip(r_f, r_t, r_l))
                identical &= same
                rec = {"width": b.width,
                       "rows": int(rows.shape[0]),
                       "rows_real": b.n_rows_valid,
                       "chunks": int(b.rows.shape[0]),
                       "fused_s": t_f, "two_step_s": t_t,
                       "legacy_scan_s": t_l,
                       "statically_skipped": False,
                       "fused_speedup_vs_two_step": t_t / t_f,
                       "fused_speedup_vs_legacy_scan": t_l / t_f,
                       "bit_identical": same}
            widths.append(rec)
            for k in tot:
                tot[k] += rec[k]
        out[f"{name}_per_width"] = widths
        # headline KERNEL speedup: non-skipped buckets only, so the number
        # measures the gather fusion itself, not the static empty-bucket skip
        real = [r for r in widths if not r["statically_skipped"]]
        for k in ("fused_s", "two_step_s", "legacy_scan_s"):
            out[f"{name}_kernel_{k}"] = sum(r[k] for r in real)
        kf = out[f"{name}_kernel_fused_s"]
        out[f"{name}_kernel_speedup_vs_two_step"] = (
            out[f"{name}_kernel_two_step_s"] / kf if kf else None)
        out[f"{name}_kernel_speedup_vs_legacy_scan"] = (
            out[f"{name}_kernel_legacy_scan_s"] / kf if kf else None)
        # ENGINE totals: the old paths evaluated every bucket, the fused
        # engine also skips the all-padding ones — skip benefit included,
        # labeled as such
        out[f"{name}_engine_fused_s"] = tot["fused_s"]
        out[f"{name}_engine_two_step_s"] = tot["two_step_s"]
        out[f"{name}_engine_legacy_scan_s"] = tot["legacy_scan_s"]
        out[f"{name}_engine_speedup_vs_two_step"] = (
            tot["two_step_s"] / tot["fused_s"] if tot["fused_s"] else None)
        out[f"{name}_engine_speedup_vs_legacy_scan"] = (
            tot["legacy_scan_s"] / tot["fused_s"] if tot["fused_s"] else None)
        out[f"{name}_bit_identical"] = identical
    print(json.dumps(out, indent=1))
    return out


def run_table_streaming(dataset: str = "com-dblp", algo: str = "both",
                        repeat: int = 3, block_rows: str | int | None = None):
    """Windowed table streaming vs the resident fast path (DESIGN.md
    §Kernels), per bucket width.

    Three variants per degree bucket, all through the Pallas kernels:

      * ``resident``  — whole tables DMA'd into VMEM scratch on grid step 0
                        (the fast path; sequential grid).
      * ``streamed``  — per-row-block table windows via scalar-prefetch
                        BlockSpecs, double-buffered by the Pallas pipeline,
                        parallel (megacore-able) grid.
      * ``two_step``  — the legacy HBM-gathered tiles + scoring kernel
                        (baseline context shared with ``gather_fusion``).

    Outputs are checked bit-identical across all three.  Per-bucket window
    stats (slot stride, window fraction of the table) quantify how much of
    each table a streamed step actually reads.  ``block_rows`` overrides
    the row-block/window granularity (``graph/ell.to_device``).
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.core import moves
    from repro.graph import datasets
    from repro.graph.ell import build_ell, grid_view, to_device
    from repro.kernels.delta_q import ops as dq_ops
    from repro.kernels.label_argmax import ops as la_ops
    from repro.kernels.local_move import ops as lm_ops

    lg = datasets.load(dataset)
    g = lg.graph
    n = g.n_max
    br = int(block_rows) if block_rows else None
    ell = to_device(g, build_ell(g), block_rows=br)
    out = {"mode": "table_streaming", "dataset": dataset, "V": lg.n,
           "E": lg.m_undirected, "block_rows_override": br}

    labels = jnp.arange(n, dtype=jnp.int32)
    labels_ext = jnp.concatenate([labels, jnp.int32([n])])
    vmask = g.vertex_mask()
    deg = g.weighted_degrees()
    vol_v = g.total_volume()
    vol_com, size_com = moves.community_aux(labels, deg, vmask, n)
    com_ext = labels_ext
    vol_ext = jnp.concatenate([vol_com, jnp.zeros((1,), vol_com.dtype)])
    size_ext = jnp.concatenate([size_com, jnp.zeros((1,), size_com.dtype)])
    deg_ext = jnp.concatenate([deg, jnp.zeros((1,), deg.dtype)])
    seed = jnp.uint32(0)

    def best_of(fn):
        res = jax.block_until_ready(fn())  # warm/compile
        t_best = None
        for _ in range(repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            dt = time.perf_counter() - t0
            t_best = dt if t_best is None else min(t_best, dt)
        return t_best, res

    def plp_two_step(r_, nb, w_):
        nbr_lab = jnp.where(nb < n, labels_ext[jnp.clip(nb, 0, n)], n)
        cur_lab = labels_ext[jnp.clip(r_, 0, n)]
        best, bs, cs = la_ops.label_argmax(
            nbr_lab, w_, cur_lab, jnp.where(r_ < n, r_, n), seed,
            tie_eps=0.25, sentinel=n, use_pallas=True)
        return best, (best >= 0) & (bs > cs)

    def louvain_two_step(r_, nb, w_):
        rows_c = jnp.clip(r_, 0, n)
        cand = jnp.where(nb < n, com_ext[jnp.clip(nb, 0, n)], n)
        best, gain = dq_ops.delta_q_argmax(
            cand_com=cand, nbr_w=w_, cur_com=com_ext[rows_c],
            deg_v=deg_ext[rows_c],
            vol_cand=vol_ext[jnp.clip(cand, 0, n)],
            vol_cur=vol_ext[jnp.clip(com_ext[rows_c], 0, n)],
            size_cand=size_ext[jnp.clip(cand, 0, n)],
            size_cur=size_ext[jnp.clip(com_ext[rows_c], 0, n)],
            vol_total=vol_v, sentinel=n, singleton_rule=True,
            use_pallas=True)
        return best, (best >= 0) & (gain > 0.0)

    def make_fused(name, table_mode, windows):
        if name == "plp":
            def f(r_, nb, w_):
                return lm_ops.local_move_plp(
                    r_, nb, w_, labels_ext, seed, tie_eps=0.25, sentinel=n,
                    use_pallas=True, windows=windows, table_mode=table_mode)
        else:
            def f(r_, nb, w_):
                return lm_ops.local_move_louvain(
                    r_, nb, w_, com_ext, vol_ext, size_ext, deg_ext, vol_v,
                    sentinel=n, singleton_rule=True,
                    use_pallas=True, windows=windows, table_mode=table_mode)
        return jax.jit(f)

    algos = ("plp", "louvain") if algo == "both" else (algo,)
    for name in algos:
        two_j = jax.jit(plp_two_step if name == "plp" else louvain_two_step)
        widths = []
        identical = True
        for b in ell.buckets:
            if b.n_rows_valid == 0:
                continue  # statically skipped by the engine either way
            rows, nbr, w = grid_view(b)
            res_j = make_fused(name, "resident", None)
            str_j = make_fused(name, "streamed", b.windows)
            t_r, r_r = best_of(lambda: res_j(rows, nbr, w))
            t_s, r_s = best_of(lambda: str_j(rows, nbr, w))
            t_t, r_t = best_of(lambda: two_j(rows, nbr, w))
            same = all(
                bool(jnp.array_equal(a, c)) and bool(jnp.array_equal(a, d))
                for a, c, d in zip(r_r, r_s, r_t))
            identical &= same
            win = b.windows
            widths.append({
                "width": b.width,
                "rows": int(rows.shape[0]),
                "rows_real": b.n_rows_valid,
                "n_blocks": int(win.win_blk.shape[0]),
                "block_rows": win.block_rows,
                "window_slot": win.slot,
                "window_frac": min(1.0, 2 * win.slot / (n + 1)),
                "resident_s": t_r,
                "streamed_s": t_s,
                "two_step_s": t_t,
                "streamed_vs_resident": t_r / t_s,
                "resident_speedup_vs_two_step": t_t / t_r,
                "bit_identical": same,
            })
        out[f"{name}_per_width"] = widths
        for k in ("resident_s", "streamed_s", "two_step_s"):
            out[f"{name}_kernel_{k}"] = sum(r[k] for r in widths)
        kr = out[f"{name}_kernel_resident_s"]
        ks = out[f"{name}_kernel_streamed_s"]
        out[f"{name}_streamed_vs_resident"] = kr / ks if ks else None
        out[f"{name}_resident_speedup_vs_two_step"] = (
            out[f"{name}_kernel_two_step_s"] / kr if kr else None)
        out[f"{name}_bit_identical"] = identical
    print(json.dumps(out, indent=1))
    return out


def run_coarse_cascade(dataset: str = "com-amazon", algo: str = "louvain",
                       repeat: int = 3, backend: str = "ell"):
    """Capacity-scheduled coarse-level cascade vs the fixed-capacity pipeline
    vs the per-level driver (DESIGN.md §Pipeline).

    Three whole-run arms, bit-identical by contract (tests/test_cascade.py):

      * ``cascade``   — ``capacity_schedule`` enabled: coarse levels descend
                        through shrinking static capacities; on ell/pallas
                        the traced per-stage re-bucketing keeps the fused
                        local_move kernels on every level.
      * ``fixed``     — ``capacity_schedule="none"``: today's single
                        full-capacity program (the parity oracle).
      * ``per_level`` — ``pipeline_fused=False``: one dispatch per level,
                        aggregation on host.

    Reports interleaved best-of totals, the Fig. 4-style phase split
    (level-0 local-moving vs everything after it — aggregation + coarse
    levels — the part the cascade shrinks), the executed stage capacities,
    and the number of stage programs compiled for the cascade (must stay
    within the schedule bound).
    """
    import importlib
    import time

    louvain_mod = importlib.import_module("repro.core.louvain")
    from repro.core.louvain import LouvainConfig, leiden, louvain
    from repro.graph import datasets

    lg = datasets.load(dataset)
    g = lg.graph
    sched = louvain_mod.auto_capacity_schedule(g.n_max, g.m_max)
    if len(sched) == 1:
        # tiny smoke-scale graphs degenerate under the auto floors; force a
        # scaled-down schedule so the cascade path itself is exercised
        sched = louvain_mod.auto_capacity_schedule(
            g.n_max, g.m_max, min_n=0,
            n_floor=max(32, g.n_max // 16), m_floor=max(128, g.m_max // 16))
    out = {"mode": "coarse_cascade", "dataset": dataset, "V": lg.n,
           "E": lg.m_undirected, "backend": backend,
           "schedule": [list(c) for c in sched]}

    algos = ("louvain", "leiden") if algo == "both" else (algo,)
    for name in algos:
        run = leiden if name == "leiden" else louvain
        base = LouvainConfig(track_modularity=False, backend=backend)
        cfgs = {
            "cascade": base.replace(capacity_schedule=sched),
            "fixed": base.replace(capacity_schedule="none"),
            "per_level": base.replace(capacity_schedule="none",
                                      pipeline_fused=False),
        }
        # warm (compile) each arm; the deterministic stage-program count is
        # the number of capacities entered (one program each) — the
        # cache-miss delta only counts NEW compiles and is run-order
        # dependent across datasets sharing a stage key
        miss0 = louvain_mod._stage_fn.cache_info().misses
        res = {"cascade": run(g, cfgs["cascade"])}
        out[f"{name}_stage_programs"] = len(res["cascade"].cascade_stages)
        out[f"{name}_stage_programs_newly_compiled"] = (
            louvain_mod._stage_fn.cache_info().misses - miss0)
        res["fixed"] = run(g, cfgs["fixed"])
        res["per_level"] = run(g, cfgs["per_level"])
        lvl0_cfg = cfgs["fixed"].replace(max_levels=1)
        run(g, lvl0_cfg)

        same = all(
            bool(jnp.array_equal(jnp.asarray(res[k].labels),
                                 jnp.asarray(res["fixed"].labels)))
            and res[k].levels == res["fixed"].levels
            and res[k].sweeps_per_level == res["fixed"].sweeps_per_level
            and res[k].n_comm_per_level == res["fixed"].n_comm_per_level
            for k in ("cascade", "per_level"))
        out[f"{name}_bit_identical"] = same

        # interleaved best-of timing so drift biases no arm; the level-0-only
        # run isolates the peeled level for the Fig. 4-style split
        timed = dict(cfgs, level0=lvl0_cfg)
        best = {k: None for k in timed}
        for _ in range(repeat):
            for k, c in timed.items():
                t0 = time.perf_counter()
                run(g, c)
                dt = time.perf_counter() - t0
                best[k] = dt if best[k] is None else min(best[k], dt)
        for k, t in best.items():
            out[f"{name}_{k}_s"] = t
        out[f"{name}_cascade_speedup_vs_fixed"] = (
            best["fixed"] / best["cascade"])
        out[f"{name}_cascade_speedup_vs_per_level"] = (
            best["per_level"] / best["cascade"])
        # everything after the peeled level 0 = aggregation + coarse levels,
        # the phase the capacity schedule shrinks (Fig. 4 phase breakdown)
        tail_c = best["cascade"] - best["level0"]
        tail_f = best["fixed"] - best["level0"]
        out[f"{name}_cascade_coarse_tail_s"] = tail_c
        out[f"{name}_fixed_coarse_tail_s"] = tail_f
        out[f"{name}_coarse_tail_speedup"] = (
            tail_f / tail_c if tail_c > 0 else None)

        r = res["cascade"]
        out[f"{name}_levels"] = r.levels
        out[f"{name}_n_comm_per_level"] = r.n_comm_per_level
        out[f"{name}_cascade_stages"] = [list(c) for c in r.cascade_stages]

        # per-level local-moving vs aggregation share from the per-level
        # driver's level-tagged timers (context for the fig4 comparison)
        res_t = run(g, cfgs["per_level"].replace(per_level_timing=True))
        split = []
        for level in range(res_t.levels):
            lm = res_t.timer.totals.get(f"L{level:02d}/local_moving", 0.0)
            ag = res_t.timer.totals.get(f"L{level:02d}/aggregation", 0.0)
            tot = lm + ag or 1e-12
            split.append({"level": level, "local_moving_s": lm,
                          "aggregation_s": ag,
                          "aggregation_share": ag / tot})
        out[f"{name}_phase_split"] = split

    # compact micro-benchmark on this graph's edge arrays: the stable
    # front-compaction primitive (graph/segment.py::compact), one
    # cumsum/scatter permutation vs the legacy full argsort
    import jax

    from repro.graph import segment as seg

    fns = {how: jax.jit(lambda m_, a_, b_, how=how: seg.compact(
        m_, (a_, b_), via=how)[0]) for how in ("scatter", "argsort")}
    for how, f in fns.items():
        jax.block_until_ready(f(g.edge_mask, g.src, g.w))   # warm
        t_best = None
        for _ in range(repeat):
            t0 = time.perf_counter()
            jax.block_until_ready(f(g.edge_mask, g.src, g.w))
            dt = time.perf_counter() - t0
            t_best = dt if t_best is None else min(t_best, dt)
        out[f"compact_{how}_s"] = t_best
    out["compact_scatter_speedup"] = (
        out["compact_argsort_s"] / out["compact_scatter_s"])
    print(json.dumps(out, indent=1))
    return out


def run_aggregation(dataset: str = "com-amazon", algo: str = "louvain",
                    repeat: int = 3, backend: str = "segment"):
    """Sort-free binned coarsening vs the one-sort oracle vs the two-step
    reference (DESIGN.md §Aggregation kernel), per level and per cascade
    stage capacity.

    The per-level driver is replayed level by level to capture every level's
    ACTUAL aggregation input (carried coarse graph + converged local-moving
    labels); each input is then shrunk to the cascade stage capacity that
    level would run under (``aggregation.shrink_graph`` — so every stage
    capacity of the schedule is exercised) and three arms are timed on it,
    interleaved best-of:

      * ``binned``    — ``remap_and_coarsen_binned`` (bitmap-cumsum remap +
                        hash-bin scatter merge; ``impl="auto"`` resolves to
                        the Pallas rank kernel on TPU, the jnp ref off-TPU —
                        the resolved impl and bin width are recorded).
      * ``sort``      — ``remap_and_coarsen``, the fused one-sort oracle.
      * ``two_step``  — ``remap_communities_sorted`` + ``coarsen_graph``
                        (one n-sort + one m-sort), the original reference.

    Outputs are checked bit-identical across all three per level (coarse
    graph contents AND remap/count).  Also reported: whole-run louvain
    end-to-end under ``aggregation="binned"`` vs ``"sort"``, and the
    Fig. 4-style per-level local-moving / aggregation split for both
    (the share the sort-free path shrinks).
    """
    import importlib
    import time

    import jax
    import jax.numpy as jnp

    louvain_mod = importlib.import_module("repro.core.louvain")
    from repro.core import aggregation
    from repro.core.engine import SweepEngine
    from repro.core.louvain import LouvainConfig, leiden, louvain
    from repro.graph import datasets
    from repro.kernels.common import pick_bin_width, resolve_bin_impl

    lg = datasets.load(dataset)
    g = lg.graph
    out = {"mode": "aggregation", "dataset": dataset, "V": lg.n,
           "E": lg.m_undirected, "backend": backend}
    cfg = LouvainConfig(track_modularity=False, backend=backend)

    # cascade stage capacities to replay under (same fallback as
    # run_coarse_cascade so smoke-scale graphs still exercise the cascade)
    sched = louvain_mod.auto_capacity_schedule(g.n_max, g.m_max)
    if len(sched) == 1:
        sched = louvain_mod.auto_capacity_schedule(
            g.n_max, g.m_max, min_n=0,
            n_floor=max(32, g.n_max // 16), m_floor=max(128, g.m_max // 16))
    out["schedule"] = [list(c) for c in sched]

    # ---- replay the per-level driver to capture each level's aggregation
    # input: (carried coarse graph, converged local-moving labels)
    pairs = []
    cur = g
    for level in range(cfg.max_levels):
        spec = louvain_mod.engine_spec(
            cfg, backend=cfg.backend if level == 0
            else louvain_mod._coarse_backend(cfg.backend))
        engine = SweepEngine(cur, spec)
        res = engine.run_phase(
            jnp.arange(cur.n_max, dtype=jnp.int32), cur.vertex_mask(),
            it0=level * 1000, seed=cfg.seed, fused=True)
        pairs.append((cur, res.labels))
        new_com, n_comm, coarse = aggregation.remap_and_coarsen(
            cur, res.labels)
        if int(n_comm) == int(cur.n_valid):
            break
        cur = coarse

    def two_step(gg, cc):
        nc, k = aggregation.remap_communities_sorted(cc, gg.vertex_mask())
        return nc, k, aggregation.coarsen_graph(gg, nc, k)

    arms = {
        "binned": lambda gg, cc: aggregation.remap_and_coarsen_binned(gg, cc),
        "sort": aggregation.remap_and_coarsen,
        "two_step": jax.jit(two_step),
    }

    per_level = []
    identical = True
    for level, (cur, com) in enumerate(pairs):
        nv, mv = int(cur.n_valid), int(cur.m_valid)
        # smallest stage capacity this level's live graph fits — where the
        # cascade would actually run this aggregation
        cap = sched[0]
        for c in sched[1:]:
            if nv <= c[0] and mv <= c[1]:
                cap = c
        if cap != (cur.n_max, cur.m_max):
            cur = aggregation.shrink_graph(cur, *cap)
            com = com[:cap[0]]
        width = pick_bin_width(cur.n_max, cur.m_max)
        impl = resolve_bin_impl("auto")

        results = {k: jax.block_until_ready(f(cur, com))
                   for k, f in arms.items()}  # warm/compile
        same = all(
            bool(jnp.array_equal(results["binned"][0], r[0]))
            and bool(jnp.array_equal(results["binned"][1], r[1]))
            and all(bool(jnp.array_equal(
                getattr(results["binned"][2], f), getattr(r[2], f)))
                for f in ("src", "dst", "w", "edge_mask", "n_valid",
                          "m_valid"))
            for r in (results["sort"], results["two_step"]))
        identical &= same

        best = {k: None for k in arms}
        for _ in range(repeat):
            for k, f in arms.items():  # interleaved so drift biases no arm
                t0 = time.perf_counter()
                jax.block_until_ready(f(cur, com))
                dt = time.perf_counter() - t0
                best[k] = dt if best[k] is None else min(best[k], dt)
        per_level.append({
            "level": level, "n_valid": nv, "m_valid": mv,
            "n_cap": cur.n_max, "m_cap": cur.m_max,
            "bin_width": width, "bin_impl": impl,
            "binned_s": best["binned"], "sort_s": best["sort"],
            "two_step_s": best["two_step"],
            "binned_speedup_vs_sort": best["sort"] / best["binned"],
            "binned_speedup_vs_two_step": best["two_step"] / best["binned"],
            "bit_identical": same,
        })
    out["per_level"] = per_level
    out["bit_identical"] = identical

    # per cascade stage capacity (the Fig. 4-style aggregation split the
    # schedule shapes) and the headline totals
    stages = {}
    for r in per_level:
        stages.setdefault((r["n_cap"], r["m_cap"]),
                          {"binned_s": 0.0, "sort_s": 0.0, "two_step_s": 0.0,
                           "levels": 0})
        s = stages[(r["n_cap"], r["m_cap"])]
        for k in ("binned_s", "sort_s", "two_step_s"):
            s[k] += r[k]
        s["levels"] += 1
    out["per_stage"] = [
        {"n_cap": c[0], "m_cap": c[1], **s,
         "binned_speedup_vs_sort": s["sort_s"] / s["binned_s"]}
        for c, s in sorted(stages.items(), reverse=True)]
    for k in ("binned_s", "sort_s", "two_step_s"):
        out[f"aggregation_{k}"] = sum(r[k] for r in per_level)
    out["aggregation_speedup_vs_sort"] = (
        out["aggregation_sort_s"] / out["aggregation_binned_s"])
    out["aggregation_speedup_vs_two_step"] = (
        out["aggregation_two_step_s"] / out["aggregation_binned_s"])

    # ---- whole-run end-to-end + per-level phase split, binned vs sort
    run_e2e = leiden if algo == "leiden" else louvain
    cfgs = {"binned": cfg, "sort": cfg.replace(aggregation="sort")}
    res_e2e = {k: run_e2e(g, c) for k, c in cfgs.items()}  # warm
    out["e2e_bit_identical"] = bool(
        jnp.array_equal(jnp.asarray(res_e2e["binned"].labels),
                        jnp.asarray(res_e2e["sort"].labels)))
    best = {k: None for k in cfgs}
    for _ in range(repeat):
        for k, c in cfgs.items():
            t0 = time.perf_counter()
            run_e2e(g, c)
            dt = time.perf_counter() - t0
            best[k] = dt if best[k] is None else min(best[k], dt)
    out[f"{algo}_binned_s"] = best["binned"]
    out[f"{algo}_sort_s"] = best["sort"]
    out[f"{algo}_e2e_speedup"] = best["sort"] / best["binned"]

    for k, c in cfgs.items():
        res_t = run_e2e(g, c.replace(pipeline_fused=False,
                                     per_level_timing=True))
        split = []
        for level in range(res_t.levels):
            lm = res_t.timer.totals.get(f"L{level:02d}/local_moving", 0.0)
            ag = res_t.timer.totals.get(f"L{level:02d}/aggregation", 0.0)
            tot = lm + ag or 1e-12
            split.append({"level": level, "local_moving_s": lm,
                          "aggregation_s": ag,
                          "aggregation_share": ag / tot})
        out[f"{algo}_phase_split_{k}"] = split

    print(json.dumps(out, indent=1))
    return out


def egonet_edges(n_graphs: int, seed: int):
    """Ego-net-scale SBM stand-ins for the serving workload, as undirected
    ``(u, v, n)`` edge lists.

    com-dblp has average degree ~6, so real ego-nets are TINY (tens of
    vertices, a few hundred directed edges) — exactly the regime where
    per-request overhead dominates and request batching pays.  Sizes
    quantize onto a handful of capacity buckets at the default menus
    (asserted in tests/test_batch.py)."""
    import numpy as np

    from repro.graph.generators import sbm

    sizes = (25, 35, 45)    # smoke and full differ in count, not scale
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_graphs):
        n = int(rng.choice(sizes))
        k = int(rng.integers(3, 6))
        u, v, _w, _t = sbm(n, k, p_in=0.35, p_out=0.03, seed=seed + 7919 * i)
        out.append((u, v, n))
    return out


def _egonet_standins(n_graphs: int, seed: int):
    """``egonet_edges`` as graphs."""
    from repro.graph.builders import from_numpy_edges

    return [from_numpy_edges(u, v, n=n)
            for u, v, n in egonet_edges(n_graphs, seed)]


def run_batch_serve(dataset: str = "com-dblp", algo: str = "both",
                    repeat: int = 3, n_graphs: int = 64, seed: int = 0,
                    backend: str = "ell"):
    """Batched many-graph engine vs a sequential single-graph loop
    (DESIGN.md §Serving).

    Two arms over the SAME workload of ``n_graphs`` ego-net stand-ins:

      * ``sequential`` — ``louvain(g)`` / ``plp(g)`` per graph, in submit
        order; per-graph latency is its cumulative completion time (request
        i waits for requests < i), the serving model without batching.
      * ``batched``    — one ``louvain_batch``/``plp_batch`` call; every
        graph's latency is the batch completion time (all requests land
        together on the flush tick).

    Both arms are warmed before timing (compiles excluded from both
    equally); the measured phase then ASSERTS zero new batch-program
    compiles (the steady-state contract of the signature-keyed program
    cache) and per-graph bitwise parity between the arms.

    The default backend is ``ell`` — the fused flagship configuration the
    PR 1-6 arc built.  Its sequential driver pays a HOST-side ELL layout
    build per request on top of per-request dispatch; the batched path
    replaces both with the on-device traced re-bucketing at the bucket's
    static menu width, which is where the bulk of the single-host speedup
    comes from (on accelerators the per-dispatch launch overhead the batch
    amortizes is far larger, and lanes run in parallel instead of
    sequentially, so the gap widens).  ``backend=segment`` shows the
    compute-bound floor: on a single-core CPU a vmapped lane costs the same
    as a sequential call, so batching buys roughly the padding overhead
    back and not much more.
    """
    import time

    import numpy as np

    from repro.core import progcache
    from repro.core.batch import louvain_batch, plp_batch
    from repro.core.louvain import LouvainConfig, louvain
    from repro.core.plp import PLPConfig, plp

    from repro.kernels.common import capacity_signature

    graphs = _egonet_standins(n_graphs, seed)
    shapes = sorted({(g.n_max, g.m_max) for g in graphs})
    sigs = sorted({tuple(capacity_signature(g.n_max, g.m_max))
                   for g in graphs})
    out = {"mode": "batch_serve", "dataset": f"{dataset}-egonet-standins",
           "backend": backend, "n_graphs": n_graphs,
           "distinct_shapes": len(shapes), "buckets": len(sigs),
           "bucket_caps": [list(s[:2]) for s in sigs],
           "cpu_count": os.cpu_count(),
           "V_total": int(sum(g.n_max for g in graphs)),
           "E_total": int(sum(g.m_max for g in graphs)) // 2}

    arms = []
    if algo in ("louvain", "both"):
        cfg = LouvainConfig(track_modularity=False, backend=backend)
        arms.append(("louvain", lambda g: louvain(g, cfg),
                     lambda gs: louvain_batch(gs, cfg),
                     lambda r: (r.labels, r.modularity)))
    if algo in ("plp", "both"):
        pcfg = PLPConfig(backend=backend)
        arms.append(("plp", lambda g: plp(g, pcfg),
                     lambda gs: plp_batch(gs, pcfg),
                     lambda r: (r.labels, r.iterations)))

    for name, single, batch, key in arms:
        # ---- parity + warmup (compiles excluded from both arms equally)
        oracle = [single(g) for g in graphs]
        batched = batch(graphs)
        for i, (o, b) in enumerate(zip(oracle, batched)):
            ko, kb = key(o), key(b)
            assert np.array_equal(ko[0], kb[0]) and ko[1:] == kb[1:], (
                f"{name}: batched result differs from unbatched oracle "
                f"for graph {i}")
        out[f"{name}_bitwise_ok"] = True

        # ---- steady state: zero new batch programs during measurement
        stats0 = progcache.cache_stats()[f"batch.{name}"]
        seq_best = bat_best = None
        seq_lat = bat_lat = None
        for _ in range(repeat):           # interleaved A/B best-of
            t0 = time.perf_counter()
            lat = []
            for g in graphs:
                single(g)
                lat.append(time.perf_counter() - t0)
            dt = time.perf_counter() - t0
            if seq_best is None or dt < seq_best:
                seq_best, seq_lat = dt, lat
            t0 = time.perf_counter()
            batch(graphs)
            dt = time.perf_counter() - t0
            if bat_best is None or dt < bat_best:
                bat_best, bat_lat = dt, [dt] * len(graphs)
        stats1 = progcache.cache_stats()[f"batch.{name}"]
        recompiles = stats1["misses"] - stats0["misses"]
        assert recompiles == 0, (
            f"{name}: {recompiles} batch-program recompiles in steady state")
        out[f"{name}_recompiles_measured"] = recompiles
        out[f"{name}_program_cache"] = stats1

        out[f"{name}_sequential_s"] = seq_best
        out[f"{name}_batched_s"] = bat_best
        out[f"{name}_throughput_sequential_gps"] = n_graphs / seq_best
        out[f"{name}_throughput_batched_gps"] = n_graphs / bat_best
        out[f"{name}_throughput_speedup"] = seq_best / bat_best
        for arm, lat in (("sequential", seq_lat), ("batched", bat_lat)):
            out[f"{name}_{arm}_p50_ms"] = float(np.percentile(lat, 50)) * 1e3
            out[f"{name}_{arm}_p99_ms"] = float(np.percentile(lat, 99)) * 1e3

    print(json.dumps(out, indent=1))
    return out


def run_serve_resilience(dataset: str = "com-dblp", repeat: int = 1,
                         ticks: int = 90, per_tick: int = 8,
                         n_graphs: int = 6, seed: int = 0,
                         tick_sleep_s: float = 0.02):
    """Steady-state serving under injected transient faults
    (DESIGN.md §Resilience) — the measurement behind the deadline/retry/
    breaker machinery.

    Three arms over the SAME submit/flush tick loop, differing only in the
    ``transient_batch_fail`` schedule (deterministic Bresenham rate, so
    runs are reproducible):

      * ``fault_0pct``  — production clean path: the resilience layer must
        cost ~nothing when nothing fails.
      * ``fault_5pct``  — 5% of dispatch attempts fail, ISOLATED fires:
        the jittered-backoff retry absorbs every one (expect ok == served,
        retries > 0, zero sequential fallbacks, zero breaker trips).
      * ``fault_20pct`` — 20% of dispatch attempts fail in bursts of 9
        consecutive fires (a poisoned recompile storm): bursts outlast the
        retry budget, chunks fail through to the sequential ladder, the
        per-signature breaker trips, sheds load at the door, half-open-
        probes back after ``breaker_reset_s`` — recovery time is the
        observed breaker-open duration.

    Each arm warms its compiled programs UNDER ITS OWN fault-set cache key
    (armed but rate 0) so the measured phase is steady-state for batch AND
    sequential-fallback programs alike: traffic cycles over ``n_graphs``
    DISTINCT edge lists, and the warm runs every one through both the
    batched path and the single-graph ladder (single-graph programs are
    exact-shape-keyed, so an unwarmed shape would hide a multi-second
    compile inside the measured fallback).  ``tick_sleep_s`` models the
    transport's batching-tick cadence — it is what lets the breaker's
    reset window elapse in wall-clock so the 20% arm demonstrates a full
    trip → shed → probe → close cycle.
    """
    import time as _time

    import numpy as np

    from launch.community_serve import (CommunityRequest,
                                        CommunityServeEngine)
    from repro.core.louvain import LouvainConfig, louvain
    from repro.graph.generators import sbm
    from repro.utils import faultinject, telemetry

    FAULT = "transient_batch_fail"
    # (arm, rate, burst): burst 9 = 3 consecutive chunk outcomes of
    # (1 attempt + 2 retries) each — exactly what defeats max_retries=2
    # and feeds breaker_threshold=3 consecutive failures
    arms_spec = (("fault_0pct", 0.0, 1),
                 ("fault_5pct", 0.05, 1),
                 ("fault_20pct", 0.2 / 9, 9))

    rng = np.random.default_rng(seed)
    sizes = (25, 35, 45)
    workload = []
    for i in range(n_graphs):
        n = int(rng.choice(sizes))
        k = int(rng.integers(3, 6))
        u, v, _w, _t = sbm(n, k, p_in=0.35, p_out=0.03, seed=seed + 613 * i)
        workload.append((u, v, n))

    cfg = LouvainConfig(track_modularity=False)
    out = {"mode": "serve_resilience",
           "dataset": f"{dataset}-egonet-standins",
           "n_graphs": n_graphs, "ticks": ticks, "per_tick": per_tick,
           "max_retries": 2, "breaker_threshold": 3, "breaker_reset_s": 0.5,
           "cpu_count": os.cpu_count(), "arms": []}

    for arm_name, rate, burst in arms_spec:
        telemetry.reset()
        eng = CommunityServeEngine(
            louvain_cfg=cfg, max_batch=16, max_retries=2,
            backoff_base_s=0.01, breaker_threshold=3, breaker_reset_s=0.5)

        # ---- warm under this arm's fault-set cache key (armed, never
        # firing): batched programs via a flush, the sequential-ladder
        # single-graph programs via one direct run per size class
        if rate > 0:
            faultinject.arm(FAULT)
            faultinject.set_rate(FAULT, 0.0)
        try:
            from repro.graph.builders import from_numpy_edges_robust
            for j, (u, v, n) in enumerate(workload):
                eng.submit(CommunityRequest(f"warm-{j}", u, v, n=n))
            eng.flush()
            for (u, v, n) in workload:
                g, _ = from_numpy_edges_robust(u, v, n=n)
                louvain(g, cfg)

            # ---- measured steady-state tick loop
            if rate > 0:
                faultinject.set_rate(FAULT, rate)
                faultinject.set_burst(FAULT, burst)
            served = shed = errors = 0
            lat = []
            idx = 0
            t0 = _time.perf_counter()
            for _tick in range(ticks):
                if tick_sleep_s:
                    _time.sleep(tick_sleep_s)
                for _ in range(per_tick):
                    u, v, n = workload[idx % len(workload)]
                    idx += 1
                    r = eng.submit(CommunityRequest(
                        f"{arm_name}-{idx}", u, v, n=n))
                    if r is not None:
                        shed += 1
                for resp in eng.flush():
                    if resp.ok:
                        served += 1
                        lat.append(resp.latency_s)
                    else:
                        errors += 1
            wall = _time.perf_counter() - t0
        finally:
            faultinject.disarm()

        c = telemetry.snapshot()
        vals = telemetry.values()
        open_s = vals.get("serve.breaker_open_s")
        arm = {
            "arm": arm_name, "fault_rate": rate, "fault_burst": burst,
            "submitted": idx, "served": served, "errors": errors,
            "shed": shed, "shed_rate": shed / idx,
            "wall_s": wall, "throughput_gps": served / wall,
            "p50_ms": float(np.percentile(lat, 50)) * 1e3 if lat else None,
            "p99_ms": float(np.percentile(lat, 99)) * 1e3 if lat else None,
            "faults_fired": c.get(f"fault.fired.{FAULT}", 0),
            "retries": c.get("serve.retry", 0),
            "sequential_fallbacks": c.get(
                "serve.batch_fallback_sequential", 0),
            "breaker_trips": c.get("serve.breaker_trip", 0),
            "breaker_closes": c.get("serve.breaker_close", 0),
            "door_rejects": c.get("serve.breaker_reject", 0),
            "recovery_s": open_s["last"] if open_s else None,
            "breakers": eng.stats()["breakers"],
        }
        out["arms"].append(arm)

    # the contract the artifact pins: no request unanswered in ANY arm
    for arm in out["arms"]:
        assert arm["submitted"] == (arm["served"] + arm["errors"]
                                    + arm["shed"]), arm["arm"]
    print(json.dumps(out, indent=1, default=str))
    return out


_MODES = {"community": run_community, "level_fusion": run_level_fusion,
          "gather_fusion": run_gather_fusion,
          "table_streaming": run_table_streaming,
          "coarse_cascade": run_coarse_cascade,
          "aggregation": run_aggregation,
          "batch_serve": run_batch_serve,
          "serve_resilience": run_serve_resilience}


def main():
    if sys.argv[1] in _MODES:
        dataset = sys.argv[2] if len(sys.argv) > 2 else "com-dblp"
        kw = {}
        for tok in sys.argv[3:]:
            k, v = tok.split("=", 1)
            kw[k] = (int(v) if k in ("repeat", "block_rows", "n_graphs",
                                     "seed", "ticks", "per_tick") else v)
        _MODES[sys.argv[1]](dataset, **kw)
        return
    arch, shape = sys.argv[1], sys.argv[2]
    overrides = {}
    serve_bf16 = False
    for tok in sys.argv[3:]:
        k, v = tok.split("=", 1)
        if k == "serve_bf16":
            serve_bf16 = v not in ("0", "false")
            continue
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v
    run(arch, shape, overrides, serve_bf16)


if __name__ == "__main__":
    main()
