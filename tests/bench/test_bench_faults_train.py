"""A train cell's check, driven through the whole harness at a CPU size:
a sound run is correct, and each fault the cell can have, planted in the
timed path, turns ``correct`` false; so does the float8 control."""
import json

import pytest

from benchtiny import failed_checks, run_devices, run_tiny, tiny_cell

CELL = "olmo-1b-4l.cvap3"


def test_sound_run_is_correct():
    out = run_tiny(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.launch import steps
    make = steps.make_train_step

    def frozen(cfg, tcfg, mesh, donate=True, unroll=False):
        real = make(cfg, tcfg, mesh, donate=False)

        def step(state, batch):
            _, metrics = real(state, batch)
            return state, metrics
        return step

    monkeypatch.setattr(steps, "make_train_step", frozen)
    out = run_tiny(CELL)
    assert not out["correct"]
    assert {"grad_norm_gap", "change_norm_gap"} <= set(failed_checks(out))


def test_half_the_batch_left_out(monkeypatch):
    from repro.models import model as M
    loss = M.lm_loss

    def half(cfg, ctx, params, ids, labels, **kw):
        h = ids.shape[0] // 2
        return loss(cfg, ctx, params, ids[:h], labels[:h], **kw)

    monkeypatch.setattr(M, "lm_loss", half)
    out = run_tiny(CELL)
    assert not out["correct"]
    assert failed_checks(out)


def test_control_and_planted_faults_fail_the_check():
    from bench.control import train_control
    cell = tiny_cell(CELL)
    rec = train_control(cell, seed=7, require_tpu=False)
    for form in ("fp8", "frozen", "half_batch"):
        assert rec[form]["correct"] is False, (form, rec[form]["checks"])
        assert set(rec[form]["checks"]) == set(cell.limits)


FOUR = """
import json
from benchtiny import run_tiny
{patch}
out = run_tiny("olmo-1b-4l.bsp.dp4")
print(json.dumps(out))
"""

NO_EXCHANGE = """
from repro.core import sync
sync._psum_tree = lambda tree, axes, compress: tree
"""


@pytest.mark.parametrize("patch, correct", [("", True),
                                            (NO_EXCHANGE, False)],
                         ids=["sound", "exchange_left_out"])
def test_four_replicas(patch, correct):
    out = json.loads(run_devices(FOUR.format(patch=patch)).splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is correct, out["checks"]
    if not correct:
        assert "change_norm_gap" in failed_checks(out)
