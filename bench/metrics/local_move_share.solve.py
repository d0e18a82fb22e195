"""Device time of the local-moving sweeps (operations in the program's
``repro.local_move`` scope) over device busy time in the window; see
``bench/scopes.py``."""
from bench import scopes


def read(run):
    s = scopes.of_run(run, scopes.checkout_of(__file__))
    return s.scope_share("repro.local_move") if s else None
