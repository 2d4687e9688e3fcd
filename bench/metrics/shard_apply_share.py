"""Share of the window the server shards spend applying update batches,
in %: the runtime's ``apply`` spans (``ServerShard._flush_updates``, with
the device apply and its block copies), clipped to the window, over active
shards x window."""


def read(run):
    d = run.data
    if "apply_ns" not in d or not d["active_shards"]:
        return None
    return 100.0 * d["apply_ns"] / (d["active_shards"]
                                    * d["runtime_window_ns"])
