"""Share of the window the workers spend blocked in the SSP clock gate,
in %: the runtime's ``block_clock`` spans, clipped to the window, over
workers x window."""


def read(run):
    d = run.data
    if "block_clock_ns" not in d or not d["workers"]:
        return None
    return 100.0 * d["block_clock_ns"] / (d["workers"]
                                          * d["runtime_window_ns"])
