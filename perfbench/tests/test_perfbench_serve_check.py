"""The serving check at a small size on the CPU: the engine's answers agree
with the plain reference; its lower-precision path and answers from the
wrong head do not."""
from perfbench_testkit import checks, drive, passes, tiny_config, tiny_traffic

CELL = "gfm_serve_screen"


def test_served_rows_match_reference():
    rec = drive(tiny_config(), tiny_traffic("screen_open"), CELL,
                seconds=0.5)
    assert checks(rec)["requests_unanswered"] == 0
    assert passes(rec, CELL), checks(rec)
    assert rec["answered_in_window"] > 0 and rec["batch_slots"] > 0


def test_lower_precision_fails():
    rec = drive(tiny_config(compute_dtype="bfloat16"),
                tiny_traffic("screen_open"), CELL, seconds=0.5)
    assert not passes(rec, CELL), checks(rec)


def wrong_head(srv):
    heads = list(srv._heads)
    srv._heads = heads[1:] + heads[:1]
    srv._exec.clear()


def test_altered_answers_fail():
    rec = drive(tiny_config(), tiny_traffic("screen_open"), CELL,
                seconds=0.5, fault=wrong_head)
    assert not passes(rec, CELL), checks(rec)
