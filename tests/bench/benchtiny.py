"""Shared helpers of the benchmark tests: a cell shrunk to a size a CPU
test can hold, run through the harness with its look for a chip skipped.

The shrunk train cell keeps its configuration's structure (for OLMo:
attention, SwiGLU, tied embedding, LayerNorm, RoPE) at toy widths, its
latent attention and experts too where the file has them; the shrunk PS
cell keeps 128 topics on a 600-word vocabulary.  Their limits are set for
these sizes from the readings of sound CPU runs (loss gap 6.5e-4 to
1.2e-3, gradient and change gaps under 0.4%) and of the float8 control
(loss gap 0.016 to 0.026, gradient gap 2.1% to 3.2%): the cells' own
limits are for their full sizes on the chip.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SRC = os.path.join(ROOT, "src")
for p in (ROOT, SRC):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                  d_head=16, d_ff=128, vocab_size=256)
# the sub-configs' widths at that size, each set only where the file's
# group has the key: a 32-wide kv latent, q/k heads of 16 + 8 rotary, v
# heads of 16; 8 routed experts, 2 a token, 32 wide, shared experts of 64
# together, leading dense layers of 96
TINY_MLA = dict(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16)
TINY_MOE = dict(n_experts=8, top_k=2, d_expert=32, d_shared=64,
                d_ff_dense=96)
TINY_TRAIN_LIMITS = {"loss_gap": 0.005, "grad_norm_gap": 0.01,
                     "change_norm_gap": 0.004}


# cells built but not in BENCHMARK.json until they are measured on the
# chip (PERF.md, Open questions): configuration, traffic, chips and the
# end-to-end metrics each would report besides setup_s; their traffic,
# drivers and references are exercised here all the same
HELD = {
    "olmo-1b-4l.bsp.dp4": ("olmo-1b-4l", "bsp.dp4", 4,
                           {"tokens_per_s": "tokens/s"}),
    "lda-nytimes.ssp2": ("lda-nytimes", "ssp2", 1,
                         {"row_updates_per_s": "rows/s",
                          "clock_ms_p90": "ms"}),
}


def held_cell(name: str):
    """A held cell at its full size, its files found as ``find_cell``
    finds a listed cell's."""
    from bench import run as R
    config, traffic, chips, e2e = HELD[name]
    setup = [m for m in R.load_json(ROOT, "BENCHMARK.json")["end_to_end"]
             if m["name"] == "setup_s"]
    limits = os.path.join(ROOT, "bench", "limits", name + ".json")
    return R.Cell(name, chips,
                  R.load_json(ROOT, "bench", "configs", config + ".json"),
                  R.load_json(ROOT, "bench", "traffic", traffic + ".json"),
                  R.load_json(limits) if os.path.isfile(limits) else {},
                  [{"name": n, "unit": u} for n, u in e2e.items()] + setup,
                  [])


def tiny_config(config: dict) -> dict:
    """A train configuration file at :data:`TINY_MODEL`'s size.  Its
    ``mla`` and ``moe`` groups, where it has them, go to :data:`TINY_MLA`
    and :data:`TINY_MOE` widths; the experts keep ``first_dense_layers``,
    with two expert layers after them, and ``experts_held`` its share of
    ``n_experts``."""
    out = {**config, **TINY_MODEL}
    for key, tiny in (("mla", TINY_MLA), ("moe", TINY_MOE)):
        if key in config:
            out[key] = {**config[key], **{k: v for k, v in tiny.items()
                                          if k in config[key]}}
    moe = config.get("moe")
    if moe is not None:
        out["n_layers"] = moe.get("first_dense_layers", 0) + 2
        if "experts_held" in moe:
            out["moe"]["experts_held"] = max(1, round(
                TINY_MOE["n_experts"] * moe["experts_held"]
                / moe["n_experts"]))
    return out


def tiny_cell(name: str):
    from bench import run as R
    cell = held_cell(name) if name in HELD else R.find_cell(name)
    if cell.kind == "train":
        cell.config = tiny_config(cell.config)
        cell.traffic.update(seq_len=32, pool_batches=6)
        cell.limits = dict(TINY_TRAIN_LIMITS)
    else:
        cell.config.update(rows=600)
        cell.traffic.update(tokens_per_clock=64, max_clock_rate=2000)
    return cell


def run_tiny(name: str, seed: int = 7, seconds: float = 0.3,
             trace: bool = False) -> dict:
    from bench import run as R
    from repro.launch import compile_cache
    saved = compile_cache.enable_compile_cache
    compile_cache.enable_compile_cache = lambda: None
    try:
        return R.execute(name, seed, seconds, trace, require_tpu=False,
                         cell=tiny_cell(name))
    finally:
        compile_cache.enable_compile_cache = saved


def failed_checks(out: dict):
    return sorted(k for k, c in out["checks"].items()
                  if not c["value"] <= c["limit"])


def run_devices(code: str, n_devices: int = 4, timeout: int = 600) -> str:
    """Run ``code`` in a fresh process with ``n_devices`` host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{n_devices}")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.abspath(__file__)), SRC, ROOT])
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    if p.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{p.stdout}\n{p.stderr}")
    return p.stdout
