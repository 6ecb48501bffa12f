"""A training run with the timed path broken underneath comes out not
correct; the sound program and the float8 control are told apart.

Each test drives everything a run does after its look for a chip (set-up,
the window, the reference, the comparison) at a size a CPU holds."""

import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny
from harness import compare
from harness.kinds import train

SEEDS = [2**31 + 17, 5]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_sound_program_is_correct(seed):
    line = tiny.run(tiny.train_cell(), seed)
    assert line["correct"], line["compared"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(line)[-1] == "compared"


@pytest.mark.parametrize("seed", SEEDS)
def test_the_float8_control_is_not_correct(seed):
    su = train.build(tiny.train_cell())
    ref = train.reference_readings(su, seed)
    ctl = train.reference_readings(su, seed, fp8=True)
    ok, rows = compare.judge(compare.train_numbers(ctl, ref), tiny.TRAIN_LIMITS)
    assert not ok, rows


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.train import optimizer

    def unchanged(cfg, state, params, grads):
        return params, state, {"grad_norm": optimizer.global_norm(grads), "lr": jnp.float32(0)}

    monkeypatch.setattr(optimizer, "apply", unchanged)
    line = tiny.run(tiny.train_cell(), SEEDS[0])
    assert not line["correct"]
    assert line["compared"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    from repro.models.model_zoo import ModelZoo

    whole = ModelZoo.loss

    def half(self, params, batch):
        h = batch["tokens"].shape[0] // 2
        return whole(self, params, {k: v[:h] for k, v in batch.items()})

    monkeypatch.setattr(ModelZoo, "loss", half)
    line = tiny.run(tiny.train_cell(), SEEDS[0])
    assert not line["correct"], line["compared"]
