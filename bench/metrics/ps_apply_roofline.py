"""Roofline share of the ``ps_apply`` kernel, in %: the HBM bytes its
batches in the window need (``bench/work.py``: distinct rows read and
written, delta rows read) at the chip's peak bandwidth, over the kernel's
summed device time in the traced window.  Bandwidth bounds it: the apply
does one add per eight bytes moved.  The kernel is found by the name of
its ``pallas_call``."""
from bench import work

KERNEL = "scatter_add_pallas"


def read(run):
    tr, applies = run.data.get("trace"), run.data.get("applies_in_window")
    if tr is None or not applies or run.peaks is None:
        return None
    t0, t1 = run.data["trace_window"]
    kernel_ns = sum(max(0, min(o.end, t1) - max(o.start, t0))
                    for ops in tr.devices.values() for o in ops
                    if o.name.split(".")[0] == KERNEL)
    if kernel_ns == 0:
        return None
    need = sum(work.ps_apply_bytes(n, d, c, it) for n, d, c, it in applies)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (kernel_ns / 1e9)
