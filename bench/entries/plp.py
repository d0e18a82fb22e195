"""``from_numpy_edges``, then ``plp()`` with its default configuration."""


def ingest(u, v, n: int):
    from repro.graph.builders import from_numpy_edges

    return from_numpy_edges(u, v, n=n)


def solve(g):
    from repro.core.plp import plp

    return plp(g)
