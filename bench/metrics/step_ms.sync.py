"""Device time per train step of the ops under the program's ``sync`` scope
(core/sync.py apply_and_sync: the delta accumulation, the consistency
trigger and any exchange), in ms: leaf ops clipped to the traced window,
averaged over the chips, over the window's steps (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "sync")
