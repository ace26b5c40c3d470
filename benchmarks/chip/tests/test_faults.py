"""The whole run, with the chip look skipped and the timed path broken
underneath, comes out not correct: once for each fault a one-chip
training cell can have.  The unbroken run comes out correct."""

import jax.numpy as jnp
import pytest

from tests import tiny


def _state_unchanged(ts):
    def step(params, opt, residual, batch):
        _, _, residual, loss, gnorm = ts(params, opt, residual, batch)
        return params, opt, residual, loss, gnorm
    return step


def _half_batch(ts):
    def step(params, opt, residual, batch):
        m = batch["loss_mask"]
        keep = jnp.arange(m.size).reshape(m.shape) < m.size // 2
        return ts(params, opt, residual, dict(batch, loss_mask=m * keep))
    return step


def _loss_altered(ts):
    def step(params, opt, residual, batch):
        p, o, r, loss, gnorm = ts(params, opt, residual, batch)
        return p, o, r, loss * 1.01, gnorm
    return step


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "loss_altered": _loss_altered}


@pytest.mark.parametrize("fault", [None, *FAULTS])
def test_fault_is_caught(fault, tmp_path, monkeypatch):
    from repro.launch import train

    if fault is not None:
        orig = train.build_train_step
        monkeypatch.setattr(
            train, "build_train_step",
            lambda *a, **k: FAULTS[fault](orig(*a, **k)))
    out = tiny.run(tmp_path, seconds=0.2)
    assert out["correct"] is (fault is None), out["compared"]
