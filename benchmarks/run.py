"""Benchmark harness — one benchmark per paper table/figure.

  table1  — dataset statistics (paper's Table I + our stand-in actuals)
  fig1    — LPA runtime:     NetworkX-LPA vs seq-LPA vs Arachne-JAX-PLP
  fig2    — Louvain runtime: NetworkX vs seq vs Arachne-JAX-Louvain
  fig3    — Louvain modularity parity across implementations
  fig4    — strong scaling of parallel Louvain over device counts,
            with the paper's phase breakdown (local-moving vs aggregation)
  sweep_fusion — fused (one while_loop/level) vs stepwise engine timings
  level_fusion — whole-run pipeline (one dispatch per louvain()) vs the
            per-level driver, with the fig4 per-level local-moving /
            aggregation split and the groupby-compaction delta
  gather_fusion — fused gather-in-kernel local_move vs the legacy two-step
            (HBM-gathered tiles + scoring kernel, ± the old lax.scan chunk
            chain), per bucket width (artifact: BENCH_gather_fusion.json)
  table_streaming — windowed streamed table layout vs the VMEM-resident
            fast path vs two-step, per bucket width, with window stats
            (artifact: BENCH_table_streaming.json)
  coarse_cascade — capacity-scheduled coarse-level cascade vs the
            fixed-capacity pipeline vs per-level, with the Fig. 4 level-0 /
            coarse-tail split, stage-program count and bit-identical check
            (artifact: BENCH_coarse_cascade.json)
  aggregation — sort-free binned coarsening vs the one-sort oracle vs the
            two-step reference, per level and per cascade stage capacity,
            with bit-identical checks and the per-level aggregation share
            for both paths (artifact: BENCH_aggregation.json)
  batch_serve — batched many-graph engine (capacity-bucketed
            louvain_batch/plp_batch) vs a sequential single-graph loop on
            an ego-net-scale serving workload: throughput, p50/p99 latency,
            per-graph bitwise parity and a steady-state zero-recompile
            check (artifact: BENCH_batch_serve.json)
  serve_resilience — steady-state serving under 0%/5%/20% injected
            transient dispatch faults: throughput/p99, shed-rate, retry
            absorption, breaker trips and recovery time
            (artifact: BENCH_serve_resilience.json)
  dist_scale — shard-local coarsening scale-out on 1/2/4/8 emulated
            devices: bit-identical check vs the replicated oracle and the
            local fused driver, comm-bytes counter (halo labels + gathered
            partial groups vs the replicated all_gather baseline), the
            Fig.-4-style phase split and per-device aggregation-work trend
            (artifact: BENCH_dist_scale.json)
  roofline— §Roofline tables from the dry-run artifacts (see roofline.py)

Artifacts: benchmarks/artifacts/<name>.json (+ printed tables).
Usage: PYTHONPATH=src python -m benchmarks.run [names...]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

ART = os.path.join(os.path.dirname(os.path.abspath(__file__)), "artifacts")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BENCH_DATASETS = ["com-amazon", "com-dblp", "com-youtube", "as-skitter",
                  "com-livejournal", "com-orkut"]


def _save(name: str, obj) -> None:
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, f"{name}.json"), "w") as f:
        json.dump(obj, f, indent=1, default=str)


def _time(fn, *a, repeat=3, **kw):
    best = None
    out = None
    for _ in range(repeat):
        t0 = time.time()
        out = fn(*a, **kw)
        dt = time.time() - t0
        best = dt if best is None else min(best, dt)
    return best, out


# ------------------------------------------------------------------ table I


def bench_table1():
    from repro.graph import datasets
    rows = []
    for name in BENCH_DATASETS:
        lg = datasets.load(name)
        rows.append({
            "graph": name,
            "paper_V": lg.meta.paper_vertices, "paper_E": lg.meta.paper_edges,
            "paper_diam": lg.meta.paper_diameter,
            "standin_V": lg.n, "standin_E": lg.m_undirected,
            "standin_kind": lg.meta.description,
        })
    _save("table1_datasets", rows)
    print(f"{'graph':18s} {'paper |V|':>11s} {'paper |E|':>12s} "
          f"{'ours |V|':>9s} {'ours |E|':>10s}  kind")
    for r in rows:
        print(f"{r['graph']:18s} {r['paper_V']:>11,d} {r['paper_E']:>12,d} "
              f"{r['standin_V']:>9,d} {r['standin_E']:>10,d}  {r['standin_kind']}")
    return rows


# ------------------------------------------------------------------ fig 1/2/3


def _quality(g, labels):
    from repro.core.baselines import nx_modularity
    return nx_modularity(g, np.asarray(labels))


def bench_fig1_lpa(repeat=2):
    import jax.numpy as jnp
    from repro.core.baselines import nx_lpa, seq_lpa
    from repro.core.plp import PLPConfig, plp
    from repro.graph import datasets
    rows = []
    for name in BENCH_DATASETS:
        lg = datasets.load(name)
        g = lg.graph
        t_nx = t_seq = None
        if lg.n <= 60_000:
            t_nx, lab_nx = _time(nx_lpa, g, repeat=1)
            t_seq, lab_seq = _time(seq_lpa, g, repeat=1)
        # warm once (jit), then time (single timed run on the big graphs)
        cfg = PLPConfig(max_iterations=60)
        plp(g, cfg)
        rep = repeat if lg.n <= 50_000 else 1
        t_jax, r = _time(lambda: plp(g, cfg), repeat=rep)
        rows.append({
            "graph": name, "V": lg.n, "E": lg.m_undirected,
            "networkx_s": t_nx, "seq_python_s": t_seq, "arachne_jax_s": t_jax,
            "speedup_vs_nx": (t_nx / t_jax) if t_nx else None,
            "iterations": r.iterations,
        })
        print(f"[fig1] {name:18s} nx={t_nx and f'{t_nx:6.2f}s' or '   n/a'} "
              f"seq={t_seq and f'{t_seq:6.2f}s' or '   n/a'} "
              f"jax={t_jax:6.2f}s "
              f"speedup={t_nx and f'{t_nx/t_jax:5.1f}x' or '  -'}")
    _save("fig1_lpa_runtime", rows)
    return rows


def bench_fig2_fig3_louvain(repeat=2):
    from repro.core.baselines import nx_louvain, seq_louvain, nx_modularity
    from repro.core.louvain import LouvainConfig, louvain
    from repro.graph import datasets
    rows = []
    for name in BENCH_DATASETS:
        lg = datasets.load(name)
        g = lg.graph
        t_nx = q_nx = t_seq = q_seq = None
        if lg.n <= 60_000:
            t_nx, lab_nx = _time(nx_louvain, g, repeat=1)
            q_nx = _quality(g, lab_nx)
            t_seq, lab_seq = _time(seq_louvain, g, repeat=1)
            q_seq = _quality(g, lab_seq)
        cfg = LouvainConfig(track_modularity=False)
        if lg.n <= 50_000:
            louvain(g, cfg)  # warm (compile); big graphs: one cold timed run
        rep = repeat if lg.n <= 50_000 else 1
        t_jax, res = _time(lambda: louvain(g, cfg), repeat=rep)
        q_jax = float(res.modularity)
        rows.append({
            "graph": name, "V": lg.n, "E": lg.m_undirected,
            "networkx_s": t_nx, "seq_python_s": t_seq, "arachne_jax_s": t_jax,
            "speedup_vs_nx": (t_nx / t_jax) if t_nx else None,
            "Q_networkx": q_nx, "Q_seq": q_seq, "Q_arachne_jax": q_jax,
            "levels": res.levels, "n_communities": int(res.n_communities),
        })
        print(f"[fig2/3] {name:18s} "
              f"nx={t_nx and f'{t_nx:6.2f}s' or '   n/a'} "
              f"jax={t_jax:6.2f}s "
              f"Q(nx)={q_nx and f'{q_nx:.4f}' or '  -  '} Q(jax)={q_jax:.4f}")
    _save("fig2_louvain_runtime_fig3_modularity", rows)
    return rows


# ------------------------------------------------------------------ fig 4


def _mesh(nd: int):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:nd]).reshape(nd), ("data",))


def _fig4_record(nd: int) -> dict:
    """One fig4 cell: com-livejournal on an ``nd``-device mesh."""
    from repro.core.distributed import distributed_louvain
    from repro.graph import datasets

    lg = datasets.load("com-livejournal")
    mesh = _mesh(nd)
    # fused pipeline (default): one dispatch for the whole level loop
    res = distributed_louvain(lg.graph, mesh)      # warm compile + run
    t0 = time.perf_counter()
    res = distributed_louvain(lg.graph, mesh)
    total = time.perf_counter() - t0
    # per-level driver: the paper's local-moving/aggregation phase breakdown
    distributed_louvain(lg.graph, mesh, pipeline_fused=False)   # warm
    t0 = time.perf_counter()
    res_pl = distributed_louvain(lg.graph, mesh, pipeline_fused=False)
    total_pl = time.perf_counter() - t0
    return {"devices": nd, "total_s": total,
            "per_level_total_s": total_pl,
            "pipeline_speedup": total_pl / total,
            "phases": dict(res_pl.timer.totals),
            "sweeps_per_level": res.sweeps_per_level,
            "n_comm_per_level": res.n_comm_per_level,
            "modularity": float(res.modularity)}


def _mesh_records(tag: str, record: str, device_counts, *args) -> list:
    """``record(nd, *args)`` for each device count.

    Under ``JAX_PLATFORMS=cpu`` each count runs in a child process given
    that many forced host devices (the device count locks when JAX first
    starts).  Anywhere else the meshes are built in this process from the
    real devices, so no child ever contends for a chip; counts above the
    devices present are dropped with a note.  A failed cell raises, which
    fails the benchmark."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        import jax

        have = len(jax.devices())
        dropped = [nd for nd in device_counts if nd > have]
        if dropped:
            print(f"[{tag}] only {have} device(s): skipping {dropped}")
        fn = globals()[record]
        return [fn(nd, *args) for nd in device_counts if nd <= have]
    rows = []
    for nd in device_counts:
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={nd}")
        code = ("import json, sys; from benchmarks import run; "
                "from repro.utils.compile_cache import use_compile_cache; "
                "use_compile_cache(); "
                f"print(json.dumps(run.{record}(*json.loads(sys.argv[1]))))")
        p = subprocess.run([sys.executable, "-c", code,
                            json.dumps([nd, *args])],
                           capture_output=True, text=True, env=env, cwd=REPO,
                           timeout=1800)
        if p.returncode != 0:
            raise RuntimeError(
                f"[{tag}] devices={nd} failed (rc={p.returncode}):\n"
                f"{p.stderr[-800:]}")
        rows.append(json.loads(p.stdout.strip().splitlines()[-1]))
    return rows


def bench_fig4_strong_scaling(device_counts=(1, 2, 4, 8)):
    rows = _mesh_records("fig4", "_fig4_record", device_counts)
    for rec in rows:
        ph = rec.get("phases", {})
        print(f"[fig4] devices={rec['devices']:3d} total={rec['total_s']:6.2f}s "
              f"Q={rec['modularity']:.4f} phases={ {k: round(v,2) for k,v in ph.items()} }")
    if rows:
        base = rows[0]["total_s"]
        for r in rows:
            r["speedup"] = base / r["total_s"]
    _save("fig4_strong_scaling", rows)
    return rows


# ------------------------------------------------------------------ dist scale


def _dist_scale_record(nd: int, dataset: str) -> dict:
    """One dist_scale cell on an ``nd``-device mesh, with its bit-identity
    and comm-bytes checks."""
    from repro.core.distributed import distributed_louvain
    from repro.core.louvain import LouvainConfig, louvain
    from repro.graph import datasets

    lg = datasets.load(dataset)
    g = lg.graph
    mesh = _mesh(nd)
    rs = distributed_louvain(g, mesh, coarsening="shard_local")     # warm compile
    t0 = time.perf_counter()
    rs = distributed_louvain(g, mesh, coarsening="shard_local")
    t_shard = time.perf_counter() - t0
    rr = distributed_louvain(g, mesh, coarsening="replicated")      # warm compile
    t0 = time.perf_counter()
    rr = distributed_louvain(g, mesh, coarsening="replicated")
    t_repl = time.perf_counter() - t0
    rl = louvain(g, LouvainConfig())
    # bit-identical: shard-local == replicated oracle == local fused driver
    assert np.array_equal(rs.labels, rr.labels)
    assert np.array_equal(rs.labels, np.asarray(rl.labels))
    assert rs.modularity == rr.modularity == float(rl.modularity)
    assert rs.sweeps_per_level == rr.sweeps_per_level == rl.sweeps_per_level
    assert rs.n_comm_per_level == rr.n_comm_per_level == rl.n_comm_per_level
    assert not rs.run_report.degradations
    # comm-bytes counter: the per-level collective payload must stay
    # O(boundary + communities), never the replicated all_gather's O(m)
    cs = rs.comm_stats
    rep_bytes = cs["bytes_per_level_model"]["replicated"]
    assert all(b < rep_bytes for b in cs["actual_bytes_per_level"])
    # fig4-style phase split from the per-level reference driver
    distributed_louvain(g, mesh, pipeline_fused=False)              # warm
    t0 = time.perf_counter()
    rp = distributed_louvain(g, mesh, pipeline_fused=False)
    t_pl = time.perf_counter() - t0
    m_pad, h_cap = cs["m_pad"], cs["halo_cap"]
    return {
        "devices": nd, "graph": dataset,
        "V": int(lg.n), "E": int(lg.m_undirected),
        "shard_local_total_s": t_shard, "replicated_total_s": t_repl,
        "per_level_total_s": t_pl,
        "phases_fused": dict(rs.timer.totals),
        "phases_per_level": dict(rp.timer.totals),
        "modularity": rs.modularity, "levels": rs.levels,
        "m_pad": m_pad, "halo_cap": h_cap,
        "agg_rows_per_device_shard_local": m_pad + nd * h_cap,
        "agg_rows_per_device_replicated": nd * m_pad,
        "comm_bytes_model": cs["bytes_per_level_model"],
        "actual_bytes_per_level": cs["actual_bytes_per_level"],
        "gathered_groups_per_level": cs["gathered_groups_per_level"],
        "halo_labels": cs["halo_labels"],
        "partition_stats": rs.partition_stats,
        "bit_identical": True,
    }


def bench_dist_scale(device_counts=(1, 2, 4, 8), dataset="com-dblp"):
    """Shard-local coarsening scale-out (DESIGN.md §Distributed pipeline):
    per device count, bit-identical check (shard_local vs replicated oracle
    vs local fused driver), the measured collective payload vs the
    replicated all_gather baseline, and the per-device aggregation-work
    trend that carries the weak-scaling claim on emulated meshes."""
    rows = _mesh_records("dist_scale", "_dist_scale_record", device_counts,
                         dataset)
    for rec in rows:
        nd = rec["devices"]
        model = rec["comm_bytes_model"]
        print(f"[dist_scale] devices={nd:2d} "
              f"shard_local={rec['shard_local_total_s']:6.2f}s "
              f"replicated={rec['replicated_total_s']:6.2f}s "
              f"Q={rec['modularity']:.4f} "
              f"agg_rows/dev={rec['agg_rows_per_device_shard_local']:,d} "
              f"(repl {rec['agg_rows_per_device_replicated']:,d})  "
              f"bytes/level model shard={model['shard_local']:,d} "
              f"repl={model['replicated']:,d} "
              f"actual={rec['actual_bytes_per_level']}")
        pq = rec["partition_stats"]
        print(f"    partition imbalance={pq['imbalance']:.3f} "
              f"cut={pq['cut_fraction']:.1%} halo_factor={pq['halo_factor']:.2f} "
              f"ghosts={pq['total_ghosts']:,d}  "
              f"phases={ {k: round(v, 3) for k, v in rec['phases_per_level'].items()} }")
    # weak-scaling invariant: per-device aggregation work shrinks with the
    # mesh (m_pad ~ m/D while the merge stays O(D * h_cap))
    if len(rows) >= 2:
        assert (rows[-1]["agg_rows_per_device_shard_local"]
                < rows[0]["agg_rows_per_device_shard_local"]), \
            "per-device aggregation work did not shrink with the mesh"
    # smoke runs (REPRO_DATASET_SCALE set) must not clobber the committed
    # full-scale baseline artifact
    suffix = "_smoke" if os.environ.get("REPRO_DATASET_SCALE") else ""
    _save(f"BENCH_dist_scale{suffix}", rows)
    return rows


# ------------------------------------------------------------------ sweep fusion


def bench_sweep_fusion(datasets=("com-amazon", "com-dblp")):
    """Fused (one while_loop per level) vs stepwise (per-sweep dispatch)
    engine timings — the measurement for DESIGN.md §Engine."""
    from benchmarks.perf_variants import run_community
    rows = []
    for name in datasets:
        rec = run_community(name, algo="both", repeat=2)
        rows.append(rec)
        print(f"[fusion] {name:18s} "
              f"plp {rec['plp_stepwise_s']:.3f}s -> {rec['plp_fused_s']:.3f}s "
              f"({rec['plp_fused_speedup']:.2f}x)  "
              f"louvain {rec['louvain_stepwise_s']:.3f}s -> "
              f"{rec['louvain_fused_s']:.3f}s "
              f"({rec['louvain_fused_speedup']:.2f}x)")
    _save("sweep_fusion", rows)
    return rows


# ------------------------------------------------------------------ level fusion


def bench_level_fusion(datasets=("com-amazon", "com-dblp")):
    """Whole-run pipeline fusion vs per-level driver (DESIGN.md §Pipeline),
    with the paper's fig4 phase breakdown per level."""
    from benchmarks.perf_variants import run_level_fusion
    rows = []
    for name in datasets:
        rec = run_level_fusion(name, algo="louvain", repeat=4)
        rows.append(rec)
        print(f"[level_fusion] {name:18s} "
              f"louvain {rec['louvain_per_level_s']:.3f}s -> "
              f"{rec['louvain_pipeline_s']:.3f}s "
              f"({rec['louvain_pipeline_speedup']:.2f}x)  "
              f"groupby 2-sort {rec['groupby_argsort_s']*1e3:.2f}ms -> "
              f"1-sort {rec['groupby_scatter_s']*1e3:.2f}ms "
              f"({rec['groupby_scatter_speedup']:.2f}x)")
        for s in rec["louvain_phase_split"]:
            print(f"    L{s['level']:02d} local_moving={s['local_moving_s']:.4f}s "
                  f"aggregation={s['aggregation_s']:.4f}s "
                  f"(agg share {s['aggregation_share']:.1%})")
    _save("level_fusion", rows)
    return rows


# ------------------------------------------------------------------ gather fusion


def bench_gather_fusion(datasets=("com-dblp",)):
    """Fused gather-in-kernel local_move vs the legacy two-step path
    (DESIGN.md §Kernels) — the measurement behind the local_move kernel."""
    from benchmarks.perf_variants import run_gather_fusion
    rows = []
    for name in datasets:
        rec = run_gather_fusion(name, algo="both", repeat=3)
        rows.append(rec)
        for alg in ("plp", "louvain"):
            ks = rec[f"{alg}_kernel_speedup_vs_two_step"]
            es = rec[f"{alg}_engine_speedup_vs_two_step"]
            print(f"[gather_fusion] {name:18s} {alg:8s} kernel "
                  f"two-step {rec[f'{alg}_kernel_two_step_s']*1e3:.2f}ms -> "
                  f"fused {rec[f'{alg}_kernel_fused_s']*1e3:.2f}ms "
                  f"({ks and f'{ks:.2f}x' or 'n/a'})  "
                  f"engine+skip {es and f'{es:.2f}x' or 'n/a'}  "
                  f"bit_identical={rec[f'{alg}_bit_identical']}")
    # smoke runs (REPRO_DATASET_SCALE set) must not clobber the committed
    # full-scale baseline artifact
    suffix = "_smoke" if os.environ.get("REPRO_DATASET_SCALE") else ""
    _save(f"BENCH_gather_fusion{suffix}", rows)
    return rows


# ------------------------------------------------------------------ table streaming


def bench_table_streaming(datasets=("com-dblp",)):
    """Windowed table streaming vs resident fast path (DESIGN.md §Kernels) —
    the measurement behind the beyond-VMEM local_move layout."""
    from benchmarks.perf_variants import run_table_streaming
    rows = []
    for name in datasets:
        rec = run_table_streaming(name, algo="both", repeat=3)
        rows.append(rec)
        for alg in ("plp", "louvain"):
            sr = rec[f"{alg}_streamed_vs_resident"]
            rt = rec[f"{alg}_resident_speedup_vs_two_step"]
            print(f"[table_streaming] {name:18s} {alg:8s} "
                  f"resident {rec[f'{alg}_kernel_resident_s']*1e3:.2f}ms  "
                  f"streamed {rec[f'{alg}_kernel_streamed_s']*1e3:.2f}ms "
                  f"(streamed/resident {sr and f'{1/sr:.2f}x' or 'n/a'})  "
                  f"resident-vs-two-step {rt and f'{rt:.2f}x' or 'n/a'}  "
                  f"bit_identical={rec[f'{alg}_bit_identical']}")
            for r in rec[f"{alg}_per_width"]:
                print(f"    W={r['width']:<5d} rows={r['rows_real']:<8d} "
                      f"blocks={r['n_blocks']:<5d} "
                      f"window={r['window_frac']:.1%} of table  "
                      f"resident={r['resident_s']*1e3:.2f}ms "
                      f"streamed={r['streamed_s']*1e3:.2f}ms")
    # smoke runs (REPRO_DATASET_SCALE set) must not clobber the committed
    # full-scale baseline artifact
    suffix = "_smoke" if os.environ.get("REPRO_DATASET_SCALE") else ""
    _save(f"BENCH_table_streaming{suffix}", rows)
    return rows


# ------------------------------------------------------------------ coarse cascade


def bench_coarse_cascade(datasets=("com-amazon",)):
    """Capacity-scheduled cascade vs fixed-capacity pipeline vs per-level
    driver (DESIGN.md §Pipeline) — the measurement behind the shrink-aware
    coarse-level machinery.  com-amazon is the deep-hierarchy dataset the
    issue targets (10 coarsening levels on the stand-in)."""
    from benchmarks.perf_variants import run_coarse_cascade
    rows = []
    for name in datasets:
        rec = run_coarse_cascade(name, algo="louvain", repeat=3)
        rows.append(rec)
        sp = rec["louvain_cascade_speedup_vs_fixed"]
        ts = rec["louvain_coarse_tail_speedup"]
        print(f"[coarse_cascade] {name:18s} "
              f"fixed {rec['louvain_fixed_s']:.3f}s -> "
              f"cascade {rec['louvain_cascade_s']:.3f}s ({sp:.2f}x)  "
              f"coarse+agg tail {rec['louvain_fixed_coarse_tail_s']:.3f}s -> "
              f"{rec['louvain_cascade_coarse_tail_s']:.3f}s "
              f"({ts and f'{ts:.2f}x' or 'n/a'})  "
              f"stages={[c[0] for c in rec['louvain_cascade_stages']]} "
              f"programs={rec['louvain_stage_programs']}"
              f"<={len(rec['schedule'])}  "
              f"bit_identical={rec['louvain_bit_identical']}")
    # smoke runs (REPRO_DATASET_SCALE set) must not clobber the committed
    # full-scale baseline artifact
    suffix = "_smoke" if os.environ.get("REPRO_DATASET_SCALE") else ""
    _save(f"BENCH_coarse_cascade{suffix}", rows)
    return rows


# ------------------------------------------------------------------ aggregation


def bench_aggregation(datasets=("com-amazon", "com-dblp")):
    """Sort-free binned coarsening vs the one-sort oracle vs two-step
    (DESIGN.md §Aggregation kernel) — the measurement behind replacing the
    coarsening GroupBy's lax.sort with the binned scatter kernel."""
    from benchmarks.perf_variants import run_aggregation
    rows = []
    for name in datasets:
        rec = run_aggregation(name, algo="louvain", repeat=3)
        rows.append(rec)
        sp = rec["aggregation_speedup_vs_sort"]
        print(f"[aggregation] {name:18s} "
              f"sort {rec['aggregation_sort_s']*1e3:.2f}ms -> "
              f"binned {rec['aggregation_binned_s']*1e3:.2f}ms ({sp:.2f}x)  "
              f"two-step {rec['aggregation_two_step_s']*1e3:.2f}ms  "
              f"e2e {rec['louvain_e2e_speedup']:.2f}x  "
              f"bit_identical={rec['bit_identical']}")
        for r in rec["per_level"]:
            print(f"    L{r['level']:02d} cap=({r['n_cap']},{r['m_cap']}) "
                  f"W={r['bin_width']} impl={r['bin_impl']} "
                  f"sort={r['sort_s']*1e3:.2f}ms "
                  f"binned={r['binned_s']*1e3:.2f}ms "
                  f"({r['binned_speedup_vs_sort']:.2f}x)")
    # smoke runs (REPRO_DATASET_SCALE set) must not clobber the committed
    # full-scale baseline artifact
    suffix = "_smoke" if os.environ.get("REPRO_DATASET_SCALE") else ""
    _save(f"BENCH_aggregation{suffix}", rows)
    return rows


# ------------------------------------------------------------------ batch serve


def bench_batch_serve(datasets=("com-dblp",)):
    """Batched many-graph engine vs a sequential single-graph loop
    (DESIGN.md §Serving) — the measurement behind ``louvain_batch``/
    ``plp_batch`` and the request-batching service."""
    from benchmarks.perf_variants import run_batch_serve
    smoke = bool(os.environ.get("REPRO_DATASET_SCALE"))
    # full scale records the flagship fused (ell) serving configuration AND
    # the segment compute-bound floor; smoke keeps CI to one backend
    backends = ("ell",) if smoke else ("ell", "segment")
    rows = []
    for name in datasets:
        for backend in backends:
            rec = run_batch_serve(name, algo="both", repeat=3,
                                  n_graphs=16 if smoke else 64,
                                  backend=backend)
            rows.append(rec)
            for alg in ("plp", "louvain"):
                print(f"[batch_serve] {name:14s} {backend:8s} {alg:8s} "
                      f"seq {rec[f'{alg}_throughput_sequential_gps']:.1f} g/s -> "
                      f"batched {rec[f'{alg}_throughput_batched_gps']:.1f} g/s "
                      f"({rec[f'{alg}_throughput_speedup']:.2f}x)  "
                      f"p99 {rec[f'{alg}_sequential_p99_ms']:.1f}ms -> "
                      f"{rec[f'{alg}_batched_p99_ms']:.1f}ms  "
                      f"bitwise_ok={rec[f'{alg}_bitwise_ok']} "
                      f"recompiles={rec[f'{alg}_recompiles_measured']}")
    # smoke runs (REPRO_DATASET_SCALE set) must not clobber the committed
    # full-scale baseline artifact
    suffix = "_smoke" if os.environ.get("REPRO_DATASET_SCALE") else ""
    _save(f"BENCH_batch_serve{suffix}", rows)
    return rows


# ------------------------------------------------------------- serve resilience


def bench_serve_resilience(datasets=("com-dblp",)):
    """Steady-state serving under 0%/5%/20% injected transient faults
    (DESIGN.md §Resilience) — the measurement behind the deadline/retry/
    breaker machinery: shed-rate, breaker trips and recovery time."""
    from benchmarks.perf_variants import run_serve_resilience
    smoke = bool(os.environ.get("REPRO_DATASET_SCALE"))
    rows = []
    for name in datasets:
        rec = run_serve_resilience(name,
                                   ticks=12 if smoke else 90,
                                   per_tick=4 if smoke else 8,
                                   n_graphs=3 if smoke else 6)
        rows.append(rec)
        for arm in rec["arms"]:
            rs = arm["recovery_s"]
            p99 = arm["p99_ms"]
            print(f"[serve_resilience] {name:14s} {arm['arm']:12s} "
                  f"{arm['throughput_gps']:6.1f} g/s  "
                  f"p99={p99 and f'{p99:.1f}ms' or 'n/a'}  "
                  f"ok={arm['served']}/{arm['submitted']} "
                  f"shed={arm['shed_rate']:.1%} "
                  f"retries={arm['retries']} trips={arm['breaker_trips']} "
                  f"recovery={rs and f'{rs:.2f}s' or '-'}")
    # smoke runs (REPRO_DATASET_SCALE set) must not clobber the committed
    # full-scale baseline artifact
    suffix = "_smoke" if os.environ.get("REPRO_DATASET_SCALE") else ""
    _save(f"BENCH_serve_resilience{suffix}", rows)
    return rows


# ------------------------------------------------------------------ roofline


def bench_roofline():
    from benchmarks import roofline
    return roofline.main([])


# ------------------------------------------------------------------ driver


ALL = {
    "table1": bench_table1,
    "fig1": bench_fig1_lpa,
    "fig2_fig3": bench_fig2_fig3_louvain,
    "fig4": bench_fig4_strong_scaling,
    "sweep_fusion": bench_sweep_fusion,
    "level_fusion": bench_level_fusion,
    "gather_fusion": bench_gather_fusion,
    "table_streaming": bench_table_streaming,
    "coarse_cascade": bench_coarse_cascade,
    "aggregation": bench_aggregation,
    "batch_serve": bench_batch_serve,
    "serve_resilience": bench_serve_resilience,
    "dist_scale": bench_dist_scale,
    "roofline": bench_roofline,
}


def main(argv=None) -> int:
    """Run the named benchmarks (all by default), crash-tolerantly.

    One broken variant must not take down a whole (hours-long) sweep: each
    benchmark runs under its own try/except, failures are recorded as
    structured ``{"variant": ..., "error": ...}`` rows in the
    ``BENCH_run_status`` artifact alongside the survivors' own artifacts,
    and the exit code reports whether anything failed.
    """
    import traceback

    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    names = (argv or sys.argv[1:]) or list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        raise SystemExit(f"unknown benchmark(s) {unknown}, want {list(ALL)}")
    status = []
    for n in names:
        print(f"\n===== {n} =====")
        try:
            ALL[n]()
            status.append({"variant": n, "ok": True, "error": None})
        except KeyboardInterrupt:
            raise
        except BaseException as e:  # noqa: BLE001 — record and continue
            traceback.print_exc()
            status.append({
                "variant": n, "ok": False,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc(limit=20),
            })
            print(f"[run] {n} FAILED ({type(e).__name__}); continuing")
    _save("BENCH_run_status", {"benchmarks": status})
    failed = [s["variant"] for s in status if not s["ok"]]
    print(f"\n[run] {len(status) - len(failed)}/{len(status)} benchmarks ok"
          + (f"; failed: {failed}" if failed else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
