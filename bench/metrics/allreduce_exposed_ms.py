"""Exposed collective time per train step, in ms: on each chip, the time
of the trace's collective ops during which no other op runs, averaged over
the chips, over the steps of the traced window."""


def read(run):
    s = run.device_summary
    steps = run.data.get("steps")
    if s is None or not steps or s.n_devices < 2:
        return None
    return s.exposed_collective_ns / 1e6 / steps
