"""Louvain served: ``launch/community_serve.py``'s ``CommunityServeEngine``
with its defaults, one engine for the run (``engine``).  ``ingest`` makes
the request a caller sends (its host edge list; the engine ingests it at
``submit``), ``solve`` submits a tick's requests and flushes them, and
``signature`` is the capacity bucket the engine puts a request in
(that of its canonical sizes, ``ingest_request``)."""


def engine():
    from launch.community_serve import CommunityServeEngine

    return CommunityServeEngine()


def signature(u, v, n: int) -> tuple:
    from launch.community_serve import CommunityRequest, ingest_request

    g, _ = ingest_request(CommunityRequest("", u, v, n=n))
    return g.n_max, g.m_max


def ingest(u, v, n: int):
    from launch.community_serve import CommunityRequest

    return CommunityRequest(f"ego-{n}-{len(u)}", u, v, n=n)


def solve(eng, requests) -> list:
    """Each request submitted, then one ``flush()``: a response per
    request, in order (a request shed at ``submit`` answers with its
    ``ok=False`` response)."""
    shed = {i: r for i, r in enumerate(eng.submit(q) for q in requests)
            if r is not None}
    served = iter(eng.flush())
    return [shed[i] if i in shed else next(served)
            for i in range(len(requests))]
