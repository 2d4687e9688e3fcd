"""Device time per train step of the ops under none of the program's scopes,
in ms: the parameters' cast to the compute dtype, the final norm, copies
XLA adds, and trace ops the op_name map lacks; read only where some op ran
under a scope (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, scopes.UNSCOPED)
