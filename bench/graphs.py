"""The graphs a cell solves, driven by a configuration file.

A configuration's ``kind`` names its generator, ``generators/<kind>.py``
(``spec.generator``): copies, not imports, of the program's generators, so
that a change to the program cannot move the yardstick.  Every generator
takes its randomness from the ``stream(seed, tag)`` it is given and nothing
else, so one seed gives one input.
"""
from __future__ import annotations

import numpy as np

from bench import spec


def stream(seed: int, *tag: int) -> np.random.Generator:
    """Independent generator for ``(seed, *tag)``; any non-negative seed,
    however large."""
    return np.random.default_rng([int(seed), *[int(t) for t in tag]])


def closed_order(cfg: dict, traffic: dict, seed: int,
                 base: str = spec.BENCH):
    """The closed loop's timed graphs: the configuration's ``graph_seed``
    fixes ``traffic["graphs"]`` graphs, the same for every run; the
    run's seed orders them.  (With timed graphs drawn from the run's seed,
    the number of sweeps, and so the work, moved with the seed: runs of
    different seeds spread 3.3% where two runs of one seed differed by
    0.05%.)"""
    graph = spec.generator(cfg["kind"], base)
    k = int(traffic["graphs"])
    gs = [graph(cfg, stream(cfg["graph_seed"], 1, i)) for i in range(k)]
    return [gs[i] for i in stream(seed, 7).permutation(k)]


def check_graph(cfg: dict, seed: int, base: str = spec.BENCH):
    """A graph of the same shape drawn from the run's seed, solved after
    the window through the same entry and programs and judged with the
    timed answers, so that every run meets data it was not written on."""
    return spec.generator(cfg["kind"], base)(cfg, stream(seed, 8))
