"""Chip benchmark for the community-detection system: one cell per run.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Everything here is the yardstick: traffic generation, graph generators,
the plain references, the comparison that decides ``correct``, and the
reduction from traces and counters to metrics.  It imports the program
(``src/repro``, ``launch/``) only to drive it.
"""
