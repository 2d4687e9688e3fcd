"""Idle share of the chips over the traced window of a PS cell, in %:
1 - the union of the device's op intervals over the window, averaged over
the chips."""


def read(run):
    s = run.device_summary
    if s is None:
        return None
    return 100.0 * s.idle_share
