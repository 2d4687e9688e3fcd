"""Quickstart: train a small LM under a bounded-asynchronous consistency
model, watch the sync epochs fire, checkpoint the synchronized state.

    PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses
import tempfile

from repro.checkpoint import latest_step
from repro.configs import ConsistencySpec, TrainConfig, reduced_config
from repro.launch.train import run


def main() -> None:
    # the reduced OLMo variant runs on CPU; swap for get_config("olmo-1b")
    # and a ("data", "model") mesh (launch/mesh.py make_mesh) on real hardware
    cfg = dataclasses.replace(reduced_config("olmo-1b"), dtype="float32")

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tcfg = TrainConfig(
            arch="olmo-1b",
            steps=60,
            lr=2e-3,
            optimizer="adam",
            log_every=10,
            # the paper's CVAP: sync when 4 steps pass OR any replica's
            # unsynchronized updates exceed 0.05 — whichever first
            consistency=ConsistencySpec(model="cvap", staleness=4,
                                        value_bound=0.05),
            checkpoint_dir=ckpt_dir,
        )
        _, history = run(tcfg, cfg, mesh=None, batch_size=8, seq_len=64)
        print(f"\nfinal loss: {history[-1]['loss']:.4f} "
              f"(from {history[0]['loss']:.4f})")
        print(f"checkpoint written at step {latest_step(ckpt_dir)}")
        assert history[-1]["loss"] < history[0]["loss"]


if __name__ == "__main__":
    main()
