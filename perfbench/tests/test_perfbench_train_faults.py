"""Broken training steps at a small size on the CPU: one that leaves the
state unchanged and one that drops half the batch both fail the check."""
import jax
import pytest

from perfbench_testkit import checks, drive, passes, tiny_config, tiny_traffic


def unchanged(step):
    def broken(state, batch):
        keep = jax.tree_util.tree_map(lambda x: x.copy(), state)
        _, out = step(state, batch)
        return keep, out
    return broken


def half_batch(step):
    def broken(state, batch):
        half = {k: v[:, :max(1, v.shape[1] // 2)] for k, v in batch.items()}
        return step(state, half)
    return broken


@pytest.mark.parametrize("fault", [unchanged, half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_fails(fault):
    rec = drive(tiny_config("hydragnn-gfm"), tiny_traffic("pretrain_mtl"),
                "gfm_pretrain", fault=fault)
    assert not passes(rec, "gfm_pretrain"), checks(rec)
