"""Pallas TPU kernels for the compute hot-spots.

Each kernel lives in its own subpackage with three files:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper; dispatches kernel vs reference
  ref.py    — pure-jnp oracle the kernel is validated against

Dispatch (ops.py): the Pallas kernel runs on TPU, or anywhere when
``REPRO_PALLAS=interpret`` is set (tests validate the kernel body on CPU via
``interpret=True``); otherwise the jnp reference runs.
"""
import os

_PROBED: str = ""


def pallas_mode() -> str:
    """'off' | 'interpret' | 'on'."""
    env = os.environ.get("REPRO_PALLAS", "").lower()
    if env in ("interpret", "on", "off"):
        return env
    # the backend probe is cached: this sits on the PS apply/flush hot path
    # (ps_kernels=True calls it per batch), and the first call pays the
    # whole jax import — the answer cannot change within a process
    global _PROBED
    if not _PROBED:
        import jax
        _PROBED = "on" if jax.default_backend() == "tpu" else "off"
    return _PROBED
