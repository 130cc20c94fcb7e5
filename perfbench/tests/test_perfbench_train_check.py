"""The training check at a small size on the CPU: the program's own step
agrees with the plain reference; its lower-precision path does not."""
import pytest

from perfbench_testkit import checks, drive, passes, tiny_config, tiny_traffic

CELLS = {"gfm_pretrain": ("hydragnn-gfm", "pretrain_mtl"),
         "gfm_baseline": ("hydragnn-gfm-baseline", "pretrain_mix")}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_program_step_matches_reference(cell):
    cfg, traffic = CELLS[cell]
    rec = drive(tiny_config(cfg), tiny_traffic(traffic), cell)
    got = checks(rec)
    assert got["batch_rows_bad"] == 0
    assert passes(rec, cell), got
    assert rec["steps"] >= 1 and rec["structures"] > 0
    assert rec["unwarmed_batches"] == 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_lower_precision_fails(cell):
    cfg, traffic = CELLS[cell]
    rec = drive(tiny_config(cfg, compute_dtype="bfloat16"),
                tiny_traffic(traffic), cell)
    assert not passes(rec, cell), checks(rec)
