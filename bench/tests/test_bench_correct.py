"""``correct`` is decided by a comparison that can fail: the control and
each fault a training cell can have come out as not correct.  Run at a
tiny size on the CPU; the same readings at the cell's own size come from
``bench/calibrate.py`` on the chip, and are kept under ``bench/testdata``
to be judged by the cells' own limits."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.client as client_mod
import repro.train.train_step as train_step_mod
from bench import calibrate, check, run
from bench.tests.conftest import REPO, run_tiny

NAME = "tiny.train.oversub-scan"
CHIP_READINGS = REPO / "bench/testdata/mamba2-370m.calibration.json"


@pytest.mark.parametrize("cell", ["mamba2-370m.train.oversub-scan",
                                  "mamba2-370m.train.resident"])
def test_chip_readings_at_the_cells_size_meet_its_limits(cell):
    limits = json.loads((REPO / "bench/workloads" / f"{cell}.json")
                        .read_text())["check"]
    got = json.loads(CHIP_READINGS.read_text())

    def correct(r):
        return check.judge(dict(r, bytes_mismatch=0, nonfinite_losses=0),
                           limits)[0]

    assert len(got["program"]) >= 12 and len(got["control"]) >= 3
    assert all(correct(r) for r in got["program"])
    assert not any(correct(r) for r in got["control"])
    assert not any(correct(r) for r in got["half_batch"])


def test_control_and_half_batch_read_far_above_the_program(tiny_root):
    _, _, cell, cj = run.load_cell(NAME, tiny_root)
    got = calibrate.readings(cell, cj, seeds=[3, 2 ** 32 + 7],
                             log=lambda *_: None)
    keys = ("loss_gap", "grad_gap", "change_gap")
    for prog, ctrl, half in zip(got["program"], got["control"],
                                got["half_batch"]):
        assert all(prog[k] < 0.01 for k in keys), prog
        # each stand-in reads at least three times the program on one of
        # the numbers compared
        for other in (ctrl, half):
            assert max(other[k] / prog[k] for k in keys) >= 3, (prog, other)


def test_step_that_leaves_the_state_unchanged_is_not_correct(
        tiny_root, monkeypatch):
    monkeypatch.setattr(train_step_mod, "apply_updates",
                        lambda params, grads, state, cfg: (params, state, {}))
    out = run_tiny(tiny_root)
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_not_correct(tiny_root, monkeypatch):
    lm_loss = train_step_mod.lm_loss

    def half_loss(logits, labels, aux=None):
        h = logits.shape[0] // 2
        return lm_loss(logits[:h], labels[:h], aux)

    monkeypatch.setattr(train_step_mod, "lm_loss", half_loss)
    out = run_tiny(tiny_root)
    assert not out["correct"], out["checks"]


def test_a_byte_altered_where_it_is_served_is_not_correct(
        tiny_root, monkeypatch):
    assemble = client_mod.CacheClient._assemble

    def altered(self, plan, fetched):
        data = np.array(assemble(self, plan, fetched))
        data[len(data) // 2] ^= 0x10
        return data

    monkeypatch.setattr(client_mod.CacheClient, "_assemble", altered)
    out = run_tiny(tiny_root)
    assert not out["correct"]
    assert out["checks"]["bytes_mismatch"]["value"] > 0


def test_a_non_finite_loss_is_not_correct(tiny_root, monkeypatch):
    lm_loss = train_step_mod.lm_loss
    monkeypatch.setattr(train_step_mod, "lm_loss",
                        lambda logits, labels, aux=None:
                        lm_loss(logits, labels, aux) * jnp.nan)
    out = run_tiny(tiny_root)
    assert not out["correct"]
