"""A run with the timed path broken underneath has to come out not
correct: for each fault a one-chip check can have, planted in the
program while the harness drives the rest of a run (no device check).
The exchange between chips does not exist in a one-chip cell."""

import pytest

import control
from stateright_tpu.checker import Checker
from stateright_tpu.models.two_phase_commit_tpu import TwoPhaseSysEncoded
from test_harness import run


@pytest.fixture
def broken(monkeypatch):
    """Plant a fault with ``monkeypatch`` and drop the programs built
    before and after it."""
    control.drop_programs()
    yield monkeypatch
    monkeypatch.undo()
    control.drop_programs()


def test_step_returns_state_unchanged(spec, broken):
    broken.setattr(TwoPhaseSysEncoded, "step_slot_vec",
                   lambda self, vec, slot: vec)
    result, compared = run(spec)
    assert result["correct"] is False
    assert compared["unique_gap"]["value"] > 0


def test_half_the_batch_left_out(spec, broken):
    """Every state whose packed lanes have an odd sum enables nothing:
    about half of each wave's frontier is dropped."""
    orig = TwoPhaseSysEncoded.enabled_bits_vec

    def half(self, vec):
        import jax.numpy as jnp

        bits = orig(self, vec)
        keep = ((vec[0] + vec[1]) & jnp.uint32(1)) == 0
        return jnp.where(keep, bits, jnp.zeros_like(bits))

    broken.setattr(TwoPhaseSysEncoded, "enabled_bits_vec", half)
    result, compared = run(spec)
    assert result["correct"] is False
    assert compared["unique_gap"]["value"] > 0


def test_answer_altered_where_produced(spec, broken):
    orig = Checker.unique_state_count
    broken.setattr(Checker, "unique_state_count",
                   lambda self: orig(self) + 1)
    result, compared = run(spec)
    assert result["correct"] is False
    assert compared["unique_gap"]["value"] == 1
