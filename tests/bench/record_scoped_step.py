"""The tiny train step under the program's named scopes, and a recorder of
its trace for ``bench/testdata``.

    python tests/bench/record_scoped_step.py --out bench/testdata

compiles the shrunk ``olmo-1b-4l.cvap3`` step (``benchtiny.tiny_cell``, 2
rows of 1,024 tokens, so that attention runs its per-chunk map), writes
the compiled HLO text (``tiny_scoped_step.hlo.txt.gz``), then profiles
three steps inside the harness's ``bench.window``, ``bench.step_dispatch``
and ``bench.read_loss`` spans and writes the trace
(``tiny_scoped_step.xplane.pb.gz``) without its ``/host:metadata`` plane,
which holds only the HLO protos.  Run it on one TPU chip.
"""
import argparse
import glob
import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SEQ_LEN, BATCH = 1024, 2
HLO_FILE = "tiny_scoped_step.hlo.txt.gz"
XPLANE_FILE = "tiny_scoped_step.xplane.pb.gz"


def tiny_step(seq_len: int = SEQ_LEN, batch: int = BATCH,
              cell: str = "olmo-1b-4l.cvap3"):
    """(jitted step, init(key) -> state, batches(n) -> list of batches)
    of the train cell ``cell`` shrunk by ``benchtiny.tiny_cell``."""
    import jax
    import numpy as np

    from bench.train_cell import model_config
    from benchtiny import tiny_cell
    from repro.configs import ConsistencySpec, TrainConfig
    from repro.launch import steps as steps_lib
    from repro.launch.state import init_train_state

    shrunk = tiny_cell(cell)
    tr = shrunk.traffic
    cfg = model_config(shrunk.config, cell + "-tiny")
    tcfg = TrainConfig(arch=cfg.name, steps=1, lr=tr["lr"],
                       warmup_steps=tr["warmup_steps"],
                       optimizer=tr["optimizer"], log_every=1,
                       consistency=ConsistencySpec(**tr["policy"]))

    def init(key):
        return init_train_state(cfg, tcfg, 1, 1, key)

    def batches(n):
        rng = np.random.default_rng(0)
        out = []
        for _ in range(n):
            ids = rng.integers(0, cfg.vocab_size, (batch, seq_len + 1),
                               dtype=np.int32)
            out.append({"ids": ids[:, :-1], "labels": ids[:, 1:]})
        return out

    return steps_lib.make_train_step(cfg, tcfg, None), init, batches


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def drop_plane(buf: bytes, name: str) -> bytes:
    """An XSpace without its plane called ``name``."""
    from bench.scopes import _fields, _text
    out = bytearray()
    for num, val in _fields(buf):
        if num == 1:
            plane = buf[val[0]:val[1]]
            pname = next((_text(plane, v) for f, v in _fields(plane)
                          if f == 2), "")
            if pname == name:
                continue
        if not isinstance(val, tuple):
            raise ValueError("XSpace holds a non-message field")
        out += _varint(num << 3 | 2) + _varint(val[1] - val[0])
        out += buf[val[0]:val[1]]
    return bytes(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    step, init, batches = tiny_step()
    state = jax.jit(init)(jax.random.key(0))
    pool = [jax.device_put(b) for b in batches(4)]
    os.makedirs(args.out, exist_ok=True)
    text = step.lower(state, pool[0]).compile().as_text()
    with gzip.open(os.path.join(args.out, HLO_FILE), "wt") as f:
        f.write(text)
    state, m = step(state, pool[0])
    float(m["loss"])
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for b in pool[1:]:
            with jax.profiler.TraceAnnotation("bench.step_dispatch"):
                state, m = step(state, b)
            with jax.profiler.TraceAnnotation("bench.read_loss"):
                float(m["loss"])
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    with open(path, "rb") as f:
        buf = drop_plane(f.read(), "/host:metadata")
    with gzip.open(os.path.join(args.out, XPLANE_FILE), "wb") as f:
        f.write(buf)
    shutil.rmtree(tdir, ignore_errors=True)
    for name in (HLO_FILE, XPLANE_FILE):
        print(name, os.path.getsize(os.path.join(args.out, name)), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
