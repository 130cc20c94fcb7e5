"""The step's operation count against a hand count at a small shape, and
the peaks table."""
import pytest

import perfbench_testkit  # noqa: F401
from perfbench import flops, harness, peaks

EGNN = "reference/gfm_egnn.py"


def test_forward_flops_by_hand():
    cfg = {"reference": EGNN, "gnn_hidden": 2, "gnn_layers": 1,
           "head_hidden": 3, "head_layers": 1}
    # phi_e: (2H+1)->H->H = 5*2 + 2*2 = 14 MACs per edge
    # phi_h: 2H->H->H = 4*2 + 2*2 = 12 MACs per atom
    # energy: H->W->1 = 2*3 + 3*1 = 9 MACs per structure
    # force:  H->W->3 = 2*3 + 3*3 = 15 MACs per atom
    macs = 1 * (7 * 14 + 4 * 12) + 2 * 9 + 4 * 15
    assert harness.reference(cfg).forward_flops(
        cfg, structures=2, atoms=4, edges=7) == 2 * macs
    assert flops.train_step_flops(cfg, structures=2, atoms=4, edges=7) \
        == 6 * macs


def test_pad_is_not_counted():
    cfg = {"reference": EGNN, "gnn_hidden": 866, "gnn_layers": 4,
           "head_hidden": 889, "head_layers": 3}
    none = harness.reference(cfg).forward_flops(cfg, structures=0, atoms=0,
                                                edges=0)
    assert none == 0


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peak("TPU v5 lite", "bf16_flops_per_s") == 197e12
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert "TPU v5e" in peaks.table()["source"]
    with pytest.raises(KeyError):
        peaks.peak("cpu", "bf16_flops_per_s")
