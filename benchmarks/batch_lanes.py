"""What a batch lane costs: one ``louvain_batch`` dispatch of ``k`` live
ego-nets of one capacity signature, at ``k`` lanes (no filler) and at 8
lanes (``8 - k`` empty lanes), for k = 1, 2, 4, 8.

The ego-nets are the first ones of each signature in the
``egonet-twitter`` benchmark pool (``bench/configs/egonet-twitter.json``),
ingested as the serve engine ingests them, at each width whatever
``core.batch.LANE_ENTRIES`` would give the signature.  By default the smallest and
the largest signature of the pool are timed.  The first call of each width
compiles its program (the persistent compilation cache is off): its
time over the warm median is given as ``compile_s``, near 0 where the
program already exists.  Run from the repository root::

    PYTHONPATH=src:. python benchmarks/batch_lanes.py [--reps 10]
        [--sig 64,256 --sig 256,32768]

One JSON object per (signature, width, live lanes) on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pool_by_signature() -> dict:
    """The pool's ingested graphs, by (n_cap, m_cap), in pool order."""
    from bench import graphs, spec
    from launch.community_serve import CommunityRequest, ingest_request

    cfg = spec.load_json(os.path.join(ROOT, "bench", "configs",
                                      "egonet-twitter.json"))
    gs = spec.module("generators", "egonet").pool(
        cfg, graphs.stream(cfg["graph_seed"], 1))
    out: dict = {}
    for u, v, n in gs:
        g, _ = ingest_request(CommunityRequest("", u, v, n=n))
        out.setdefault((g.n_max, g.m_max), []).append(g)
    return out


def time_dispatch(gs, width: int, reps: int) -> dict:
    from repro.core import batch
    from repro.core.batch import louvain_batch

    # the width under test, whatever the entry budget would give
    batch.LANE_ENTRIES = width * gs[0].m_max

    t0 = time.perf_counter()
    louvain_batch(gs, max_slots=width)
    first = time.perf_counter() - t0
    warm = []
    for _ in range(reps):
        t0 = time.perf_counter()
        louvain_batch(gs, max_slots=width)
        warm.append(time.perf_counter() - t0)
    med = statistics.median(warm)
    return dict(dispatch_s=med, min_s=min(warm), max_s=max(warm),
                compile_s=first - med)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--sig", action="append", default=None,
                    help="n_cap,m_cap (default: the pool's smallest and "
                    "largest)")
    args = ap.parse_args()
    import jax

    jax.config.update("jax_enable_compilation_cache", False)

    pool = pool_by_signature()
    sigs = ([tuple(int(x) for x in s.split(",")) for s in args.sig]
            if args.sig else [min(pool), max(pool)])
    for sig in sigs:
        gs = pool[sig]
        for k in (1, 2, 4, 8):
            for w in sorted({k, 8}):
                r = time_dispatch(gs[:k], w, args.reps)
                print(json.dumps(dict(
                    device=jax.devices()[0].device_kind, n_cap=sig[0],
                    m_cap=sig[1], live=k, lanes=w, reps=args.reps, **r)),
                    flush=True)


if __name__ == "__main__":
    main()
