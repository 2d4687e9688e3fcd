"""Device time per train step of the ops under the program's ``mlp`` scope
(the feed-forward (SwiGLU) matmuls), in ms: leaf ops clipped to the traced
window, averaged over the chips, over the window's steps
(``bench/scopes.py``)."""
from bench import scopes


def read(run):
    return scopes.step_ms(run, "mlp")
