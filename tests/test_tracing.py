"""The program names its phases in the trace.

Device scopes (``jax.named_scope``) become components of every compiled
operation's ``op_name``, which the profiler reports as its ``tf_op``; host
spans (``telemetry.span``) land in the profiler's own trace.  Both are
always on and change no result: the parity suites run with them.
"""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.louvain import (LouvainConfig, _refine_spec, _stage_fn,
                                engine_spec, louvain)
from repro.graph.builders import from_numpy_edges
from repro.graph.generators import sbm

PHASES = ("repro.local_move", "repro.aggregate", "repro.modularity",
          "repro.finalize")


def _graph(seed=3):
    u, v, w, _ = sbm(120, 4, p_in=0.3, p_out=0.03, seed=seed)
    return from_numpy_edges(u, v, w)


def _stage_op_names(g, cfg) -> set:
    """Every ``op_name`` of the compiled single-capacity stage program."""
    fn = _stage_fn(engine_spec(cfg), engine_spec(cfg),
                   _refine_spec(cfg) if cfg.refine else None,
                   cfg.max_levels, cfg.track_modularity, None,
                   cfg.aggregation)
    n = g.n_max
    ar = jnp.arange(n, dtype=jnp.int32)
    hists = (jnp.full((cfg.max_levels,), jnp.nan, jnp.float32),
             jnp.full((cfg.max_levels,), -1, jnp.int32),
             jnp.full((cfg.max_levels,), -1, jnp.int32),
             jnp.full((cfg.max_levels, cfg.max_sweeps), -1, jnp.int32),
             jnp.bool_(False))
    text = fn.lower(g, None, g, jnp.uint32(cfg.seed), ar, ar, ar,
                    jnp.int32(0), hists).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("refine", [False, True], ids=["louvain", "leiden"])
def test_stage_program_carries_phase_scopes(refine):
    cfg = LouvainConfig(seed=1, refine=refine)
    names = _stage_op_names(_graph(), cfg)
    components = {c for name in names for c in name.split("/")}
    want = set(PHASES) | ({"repro.refine"} if refine else set())
    assert want <= components, sorted(want - components)
    # the level loop's sweeps sit inside the while body, under their scope
    assert any(re.search(r"/while/body/.*repro\.local_move/", n)
               for n in names)
    if refine:
        # Leiden's inner sweeps belong to the refinement, its outer scope
        assert any("repro.refine/repro.local_move/" in n for n in names)


def _spans(tmp_path, fn):
    """``{name: [(start_ns, end_ns, line)]}`` of the ``repro.*`` host events
    recorded while ``fn`` runs under the profiler."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                             "*.xplane.pb"))
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         (plane.name, line.name)))
    return out


def _nested(spans, child, parent) -> bool:
    return bool(spans.get(child)) and all(
        any(ps <= cs and ce <= pe and pl == cl
            for ps, pe, pl in spans.get(parent, ()))
        for cs, ce, cl in spans[child])


def test_louvain_emits_cascade_host_spans(tmp_path):
    g = _graph(seed=5)
    louvain(g, LouvainConfig(seed=5))   # compile outside the trace
    spans = _spans(tmp_path, lambda: louvain(g, LouvainConfig(seed=5)))
    assert len(spans["repro.louvain"]) == 1
    for child in ("repro.louvain.dispatch", "repro.louvain.readback",
                  "repro.louvain.result"):
        assert _nested(spans, child, "repro.louvain"), child
    assert len(spans["repro.louvain.readback"]) == 1


def test_ingest_emits_host_spans(tmp_path):
    u, v, w, _ = sbm(80, 4, p_in=0.3, p_out=0.05, seed=2)
    spans = _spans(tmp_path, lambda: from_numpy_edges(u, v, w,
                                                      validate=True))
    assert len(spans["repro.ingest"]) == 1
    for child in ("repro.ingest.canonicalize", "repro.ingest.to_device",
                  "repro.ingest.validate"):
        assert _nested(spans, child, "repro.ingest"), child


def test_serve_emits_host_spans(tmp_path):
    """A served tick names each host step: submit (with the ingest inside
    it), flush, and the batch engine's pack, dispatch, readback and
    unpack inside the flush."""
    from launch.community_serve import CommunityRequest, CommunityServeEngine

    reqs = []
    for i, n in enumerate((60, 70)):
        u, v, _w, _t = sbm(n, 4, p_in=0.3, p_out=0.03, seed=40 + i)
        reqs.append(CommunityRequest(request_id=f"t{i}", u=u, v=v, n=n))
    eng = CommunityServeEngine()

    def tick():
        for r in reqs:
            eng.submit(r)
        assert all(resp.ok for resp in eng.flush())

    tick()                              # compile outside the trace
    spans = _spans(tmp_path, tick)
    assert len(spans["repro.serve.submit"]) == len(reqs)
    assert len(spans["repro.serve.flush"]) == 1
    assert _nested(spans, "repro.ingest", "repro.serve.submit")
    for child in ("repro.batch.pack", "repro.batch.dispatch",
                  "repro.batch.readback", "repro.batch.unpack"):
        assert _nested(spans, child, "repro.serve.flush"), child
