"""A train cell's configuration file as the program's ``ModelConfig``
(``bench/train_cell.py`` ``model_config``), and the names its check gives
the program's matrices (``program_norms``, ``program_matrices``,
``bench/names.py``), on a tiny configuration with latent attention and
experts after a dense layer, run through ``init_train_state`` and one
train step on the CPU."""
import dataclasses
import json
import os

import numpy as np
import pytest

from benchtiny import ROOT

# latent attention (a 32-wide kv latent, q/k heads of 16 + 8 rotary, v
# heads of 16), one dense layer of 96, then 2 layers of 8 routed experts
# (2 a token) and shared experts of 64 together
TINY_MLA_MOE = {
    "kind": "train", "reference": "none", "source": "tiny test model",
    "arch_type": "moe", "n_layers": 3, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 4, "d_head": 24, "d_ff": 32, "vocab_size": 128,
    "layer_pattern": ["attn"], "norm_kind": "rmsnorm", "gated_mlp": True,
    "mla": {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16},
    "moe": {"n_experts": 8, "top_k": 2, "d_expert": 32,
            "n_shared_experts": 2, "d_shared": 64,
            "first_dense_layers": 1, "d_ff_dense": 96},
    "published": {"n_layers": 27}, "reduced": ["n_layers"],
    "deployment": "one chip", "assumed": [], "notes": "tiny",
}


def olmo_file():
    with open(os.path.join(ROOT, "bench", "configs", "olmo-1b-4l.json")) as f:
        return json.load(f)


def test_sub_configs_become_their_dataclasses():
    from bench.train_cell import model_config
    from repro.configs.base import MLAConfig, MoEConfig
    cfg = model_config(TINY_MLA_MOE, "tiny")
    assert cfg.mla == MLAConfig(**TINY_MLA_MOE["mla"])
    assert cfg.moe == MoEConfig(**TINY_MLA_MOE["moe"])
    assert cfg.arch_type == "moe" and cfg.name == "tiny"
    assert cfg.layer_pattern == ("attn",)
    assert (cfg.d_model, cfg.vocab_size, cfg.norm_kind) == (64, 128,
                                                             "rmsnorm")


def test_olmo_file_gives_the_config_it_gave():
    """The file's fields passed through, ``arch_type`` "dense", as the
    builder made them before it took sub-configs."""
    from bench.train_cell import model_config
    from repro.configs.base import ModelConfig
    f = olmo_file()
    fields = {x.name for x in dataclasses.fields(ModelConfig)}
    before = ModelConfig(name="olmo-1b", arch_type="dense",
                         source=f["source"],
                         **{k: v for k, v in f.items()
                            if k in fields and k not in ("name", "source")})
    assert model_config(f, "olmo-1b") == before


@pytest.mark.parametrize("group, key", [("moe", "d_exprt"),
                                        ("mla", "kv_rank")])
def test_an_unknown_sub_config_key_is_refused_by_name(group, key):
    from bench.train_cell import model_config
    bad = json.loads(json.dumps(TINY_MLA_MOE))
    bad[group][key] = 7
    with pytest.raises(ValueError, match=f"{group}: .*'{key}'"):
        model_config(bad, "tiny")


def _stepped(file: dict):
    """The state after one train step of ``file``'s model, its loss, and
    its matrices' norms as the check names them."""
    import jax

    from bench.train_cell import model_config, program_matrices, program_norms
    from repro.configs import ConsistencySpec, TrainConfig
    from repro.launch import steps as steps_lib
    from repro.launch.state import init_train_state
    cfg = model_config(file, "tiny")
    tcfg = TrainConfig(arch="tiny", steps=1, lr=1e-3, warmup_steps=0,
                       optimizer="adam", log_every=1,
                       consistency=ConsistencySpec(model="cvap", staleness=3,
                                                   value_bound=0.05))
    state = jax.jit(lambda k: init_train_state(cfg, tcfg, 1, 1, k))(
        jax.random.key(0))
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 17),
                                            dtype=np.int32)
    state, m = steps_lib.make_train_step(cfg, tcfg, None)(
        state, {"ids": ids[:, :-1], "labels": ids[:, 1:]})
    return state, float(m["loss"]), program_matrices(
        jax.jit(program_norms)(state.params))


def _norm(x):
    return np.sqrt(np.sum(np.square(np.asarray(x, np.float32))))


def test_every_matrix_of_latent_attention_and_experts_has_its_name():
    state, loss, named = _stepped(TINY_MLA_MOE)
    assert np.isfinite(loss)
    mla = ["kv_norm", "w_dkv", "w_uk", "w_uv", "wo", "wq"]
    want = {"embed", "head", "final_norm/scale"}
    for layer in range(3):
        want |= {f"layers/{layer}/{n}/scale" for n in ("ln1", "ln2")}
        want |= {f"layers/{layer}/mix/{n}" for n in mla}
    want |= {f"layers/0/ffn/{n}" for n in ("w_gate", "w_in", "w_out")}
    for layer in (1, 2):
        want |= {f"layers/{layer}/ffn/router"}
        want |= {f"layers/{layer}/ffn/shared/{n}"
                 for n in ("w_gate", "w_in", "w_out")}
        want |= {f"layers/{layer}/ffn/{n}/{e}"
                 for n in ("w_gate", "w_in", "w_out") for e in range(8)}
    assert set(named) == want and len(named) == 86
    p = state.params
    # replica 0; the dense layer is the prefix, the expert layers the
    # scan's two steps
    checks = {
        "layers/0/ffn/w_in": p["prefix"][0]["ffn"]["w_in"][0],
        "layers/0/mix/wq": p["prefix"][0]["mix"]["wq"][0],
        "layers/1/mix/wq": p["scan"][0]["mix"]["wq"][0, 0],
        "layers/2/mix/wq": p["scan"][0]["mix"]["wq"][0, 1],
        "layers/2/ffn/w_in/5": p["scan"][0]["ffn"]["w_in"][0, 1, 5],
        "layers/1/ffn/w_out/7": p["scan"][0]["ffn"]["w_out"][0, 0, 7],
        "layers/2/ffn/shared/w_in": p["scan"][0]["ffn"]["shared"]["w_in"][0, 1],
        "layers/1/ffn/router": p["scan"][0]["ffn"]["router"][0, 0],
        "layers/2/ln2/scale": p["scan"][0]["ln2"]["scale"][0, 1],
        "final_norm/scale": p["final_norm"]["scale"][0],
    }
    for name, x in checks.items():
        assert named[name].shape == (1,)
        np.testing.assert_allclose(named[name][0], _norm(x), rtol=1e-5,
                                   err_msg=name)


def test_layers_of_a_unit_and_the_tail_are_numbered_in_order():
    """Alternating local and global attention scans units of two layers:
    5 layers are two units (layers 0 and 1, then 2 and 3) and a tail
    (layer 4)."""
    from benchtiny import TINY_MODEL
    file = {**olmo_file(), **TINY_MODEL, "n_layers": 5,
            "attn_kind": "alternating", "window": 8}
    state, _, named = _stepped(file)
    p = state.params
    assert len(p["scan"]) == 2 and len(p["tail"]) == 1
    assert len(named) == 1 + 5 * 7
    wq = p["scan"][0]["mix"]["wq"], p["scan"][1]["mix"]["wq"]
    for name, x in {"layers/0/mix/wq": wq[0][0, 0],
                    "layers/1/mix/wq": wq[1][0, 0],
                    "layers/2/mix/wq": wq[0][0, 1],
                    "layers/3/mix/wq": wq[1][0, 1],
                    "layers/4/mix/wq": p["tail"][0]["mix"]["wq"][0],
                    "layers/4/ffn/w_out": p["tail"][0]["ffn"]["w_out"][0]
                    }.items():
        np.testing.assert_allclose(named[name][0], _norm(x), rtol=1e-5,
                                   err_msg=name)


def test_two_matrices_with_one_name_raise():
    from bench import names
    from bench.train_cell import program_matrices, program_norms
    x = np.ones((1, 4, 4), np.float32)
    tree = {"layers": [{"mix": {"wq": x}}], "prefix": [{"mix": {"wq": x}}]}
    with pytest.raises(ValueError, match="two matrices named "
                                         "'layers/0/mix/wq'"):
        program_matrices(program_norms(tree))
    with pytest.raises(ValueError, match="two matrices named 'w'"):
        names.named({"a": np.ones((1, 2)), "b": np.ones((1,))},
                    lambda path, index: "w")


# the latent attention and experts of DeepSeek-V2-Lite's config.json at
# their published widths, 8 of the 64 routed experts held
PUBLISHED_MLA_MOE = {
    **TINY_MLA_MOE, "n_layers": 5, "d_model": 2048, "n_heads": 16,
    "n_kv_heads": 16, "d_head": 192, "vocab_size": 12800,
    "mla": {"kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128},
    "moe": {"n_experts": 64, "experts_held": 8, "top_k": 6,
            "d_expert": 1408, "n_shared_experts": 2, "d_shared": 2816,
            "first_dense_layers": 1, "d_ff_dense": 10944},
}


def test_tiny_config_shrinks_the_sub_configs_it_has():
    from benchtiny import TINY_MLA, TINY_MODEL, tiny_config
    tiny = tiny_config(PUBLISHED_MLA_MOE)
    assert tiny["mla"] == TINY_MLA
    assert tiny["moe"] == {"n_experts": 8, "experts_held": 1, "top_k": 2,
                           "d_expert": 32, "n_shared_experts": 2,
                           "d_shared": 64, "first_dense_layers": 1,
                           "d_ff_dense": 96}
    assert tiny["n_layers"] == 3
    assert {k: tiny[k] for k in TINY_MODEL if k != "n_layers"} == {
        k: v for k, v in TINY_MODEL.items() if k != "n_layers"}
    # only the keys a group has are set; the file is left as it was
    bare = {**PUBLISHED_MLA_MOE, "moe": {"n_experts": 64, "top_k": 6,
                                         "d_expert": 1408}}
    del bare["mla"]
    tiny = tiny_config(bare)
    assert tiny["moe"] == {"n_experts": 8, "top_k": 2, "d_expert": 32}
    assert "mla" not in tiny and tiny["n_layers"] == 2
    assert PUBLISHED_MLA_MOE["moe"]["n_experts"] == 64


def test_tiny_cell_of_latent_attention_and_experts_compiles(monkeypatch):
    """``tiny_cell`` and ``tiny_step`` on a cell whose configuration has
    ``mla`` and ``moe`` at published widths: the program's sub-configs come
    out at the tiny widths, and the tiny step compiles with the expert
    layers under the ``mlp`` scope."""
    import jax

    from bench import run as R
    from bench import scopes
    from bench.train_cell import model_config
    from benchtiny import TINY_MLA, tiny_cell
    from record_scoped_step import tiny_step
    from repro.configs.base import MLAConfig, MoEConfig

    name = "tiny-mla-moe.cvap3"
    program = {**PUBLISHED_MLA_MOE,
               "moe": {k: v for k, v in PUBLISHED_MLA_MOE["moe"].items()
                       if k != "experts_held"}}
    find_cell = R.find_cell

    def find(cell):
        if cell != name:
            return find_cell(cell)
        olmo = find_cell("olmo-1b-4l.cvap3")
        return R.Cell(name, 1, json.loads(json.dumps(program)),
                      dict(olmo.traffic), {}, olmo.end_to_end, [])
    monkeypatch.setattr(R, "find_cell", find)
    cfg = model_config(tiny_cell(name).config, "tiny")
    assert cfg.mla == MLAConfig(**TINY_MLA)
    assert cfg.moe == MoEConfig(n_experts=8, top_k=2, d_expert=32,
                                n_shared_experts=2, d_shared=64,
                                first_dense_layers=1, d_ff_dense=96)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (3, 64, 256)
    step, init, batches = tiny_step(seq_len=128, batch=1, cell=name)
    state = jax.eval_shape(init, jax.random.key(0))
    batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         batches(1)[0])
    op_names = scopes.op_names_from_hlo(step.lower(state, batch).compile()
                                        .as_text())
    assert any("/mlp/" in v and "transpose(" in v
               for v in op_names.values())
    assert {"embed", "attention", "attention_core", "mlp", "lm_head"} <= {
        scopes.scope_of(v) for v in op_names.values()}
