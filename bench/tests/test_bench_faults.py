"""A run with the timed path broken underneath comes out not correct:
one test for each fault the cells can have."""
import pytest

from .conftest import drive, small_spec


def state_unchanged(s):
    step = s.runner._counted
    s.runner._counted = lambda state, batch: (state, step(state, batch)[1])


def half_batch_left_out(s):
    step = s.runner._counted
    s.runner._counted = lambda state, batch: step(
        state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})


def token_altered(s):
    work = s.source.pipe.work_fn

    def altered(batch, rng):
        out = work(batch, rng)
        out["tokens"] = out["tokens"].copy()
        out["tokens"][0, 0] ^= 1
        return out

    s.source.pipe.work_fn = altered


@pytest.mark.parametrize("fault,traffic", [
    (state_unchanged, "train.resident"),
    (half_batch_left_out, "train.resident"),
    (token_altered, "train.pipeline")])
def test_fault_is_not_correct(fault, traffic):
    values, correct = drive(small_spec(traffic, corpus=40), fault=fault)
    assert not correct, values
    if fault is token_altered:
        assert values["batch_mismatch"] >= 1
    if fault is state_unchanged:
        assert values["change_gap"] == pytest.approx(1.0)
        assert values["grad_gap"] == pytest.approx(1.0)
