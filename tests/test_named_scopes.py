"""The train step's named scopes reach its compiled HLO: every scope that a
``step_ms.*`` benchmark metric reads names some instruction's ``op_name``,
and the attention and MLP scopes name ops of both the forward pass and the
backward (``transpose``) pass.  Guards the scopes against a refactor that
drops one; ``bench/scopes.py`` attributes device time by them."""
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "tests", "bench")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="module")
def op_names():
    import jax

    from bench import scopes
    from record_scoped_step import tiny_step
    step, init, batches = tiny_step(seq_len=1024, batch=1)
    state = jax.eval_shape(init, jax.random.key(0))
    batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         batches(1)[0])
    return scopes.op_names_from_hlo(step.lower(state, batch).compile()
                                    .as_text())


def test_every_metric_scope_is_in_the_compiled_step(op_names):
    from bench import scopes
    found = {scopes.scope_of(v) for v in op_names.values()}
    assert set(scopes.SCOPES) <= found


@pytest.mark.parametrize("scope", ["attention", "attention_core", "mlp"])
def test_scope_runs_forward_and_backward(op_names, scope):
    from bench import scopes
    under = [v for v in op_names.values() if scopes.scope_of(v) == scope]
    assert any("transpose(" not in v for v in under), scope
    assert any("transpose(" in v for v in under), scope


def test_the_program_has_only_the_metric_scopes():
    from bench import scopes
    src = os.path.join(ROOT, "src", "repro")
    used = []
    for dirpath, _, files in os.walk(src):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    used += re.findall(r"named_scope\(\"([^\"]+)\"\)",
                                       fh.read())
    assert sorted(used) == sorted(scopes.SCOPES)
