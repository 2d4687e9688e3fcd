"""Device time per train step of the ops under the program's ``attention``
scope (the attention mixer's projections, RoPE and head reshapes), in ms:
leaf ops clipped to the traced window, averaged over the chips, over the
window's steps (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "attention")
