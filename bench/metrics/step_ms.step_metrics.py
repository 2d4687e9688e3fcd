"""Device time per train step of the ops under the program's ``step_metrics``
scope (the step's metrics: the gradient norm and the other scalars read on
the host), in ms: leaf ops clipped to the traced window, averaged over the
chips, over the window's steps (``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "step_metrics")
