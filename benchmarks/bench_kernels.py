"""Microbenchmarks of the kernel REFERENCE paths (this container is CPU-only;
the Pallas kernels target TPU and are validated by tests in interpret mode —
wall-clock here times the jnp oracle that the dry-run lowers)."""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

RNG = np.random.default_rng(0)


def _time(fn, *args, iters=3) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else None
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6     # us


def run() -> List[Dict]:
    rows = []

    from repro.kernels.flash_attention import ops as fa
    b, s, kvh, G, dh = 1, 2048, 2, 2, 64
    q = jnp.asarray(RNG.normal(0, 1, (b, s, kvh, G, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (b, s, kvh, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (b, s, kvh, dh)), jnp.float32)
    us = _time(lambda: fa.flash_attention(q, k, v, window=512))
    flops = 4 * b * kvh * G * s * 512 * dh   # banded
    rows.append({"name": "kernel_ref/flash_attention_2k_w512",
                 "us_per_call": us, "derived_gflops": flops / us / 1e3})

    from repro.kernels.ssd_scan import ops as sd
    b, l, h, p, g, n = 2, 2048, 8, 64, 1, 128
    x = jnp.asarray(RNG.normal(0, 1, (b, l, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (b, l, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.1, 1, (h,)), jnp.float32)
    B = jnp.asarray(RNG.normal(0, 1, (b, l, g, n)), jnp.float32)
    C = jnp.asarray(RNG.normal(0, 1, (b, l, g, n)), jnp.float32)
    us = _time(lambda: sd.ssd_scan(x, dt, A, B, C, chunk=256))
    rows.append({"name": "kernel_ref/ssd_scan_2k", "us_per_call": us,
                 "derived_tokens_per_s": b * l / us * 1e6})

    from repro.kernels.rglru_scan import ops as rg
    b, l, w = 2, 2048, 1024
    xx = jnp.asarray(RNG.normal(0, 1, (b, l, w)), jnp.float32)
    r = jnp.asarray(RNG.uniform(0, 1, (b, l, w)), jnp.float32)
    i = jnp.asarray(RNG.uniform(0, 1, (b, l, w)), jnp.float32)
    lam = jnp.asarray(RNG.normal(0, 1, (w,)), jnp.float32)
    us = _time(lambda: rg.rglru(xx, r, i, lam))
    rows.append({"name": "kernel_ref/rglru_2k", "us_per_call": us,
                 "derived_tokens_per_s": b * l / us * 1e6})

    from repro.kernels.vap_accum import ops as va
    n_ = 4_000_000
    pp = jnp.asarray(RNG.normal(0, 1, n_), jnp.float32)
    dd = jnp.asarray(RNG.normal(0, 0.01, n_), jnp.float32)
    uu = jnp.asarray(RNG.normal(0, 0.01, n_), jnp.float32)
    us = _time(lambda: va.vap_accum(pp, dd, uu))
    rows.append({"name": "kernel_ref/vap_accum_4M", "us_per_call": us,
                 "derived_gbytes_per_s": 5 * 4 * n_ / us / 1e3})
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
