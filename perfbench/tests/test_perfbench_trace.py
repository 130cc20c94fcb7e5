"""The trace reduction on a small hand-built trace: one device, one host
thread with the harness's spans."""
import pytest

import perfbench_testkit  # noqa: F401  (puts the repository on sys.path)
from perfbench import devtrace

# times in ps; the window runs 0..10 us, ops cover 1..3 and 2..4 (merged
# 1..4) and 6..7 us; the host is in next_batch over 4..6 us
SPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion(x)" } }
  event_metadata { key: 2 value { id: 2 name: "%dot.2 = f32[8] dot(x, y)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(1)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0 }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 4500000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "next_batch" } }
  event_metadata { key: 3 value { id: 3 name: "not_a_span" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return devtrace.reduce_space(ProfileData.from_text_proto(SPACE))


def test_busy_is_the_union_of_op_intervals_inside_the_window(reduced):
    assert reduced["window_s"] == pytest.approx(10e-6)
    assert reduced["busy_s"]["/device:TPU:0"] == pytest.approx(4e-6)
    # a device plane with no op is in the average, fully idle
    assert reduced["busy_s"]["/device:TPU:1"] == 0.0


def test_device_ops_are_summed_by_name_and_clipped(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["fusion.1"] == pytest.approx(3e-6)
    assert ops["dot.2"] == pytest.approx(2e-6)   # the op at 11 us is out


def test_idle_gaps_are_named_by_the_host_span(reduced):
    gaps = reduced["idle_gaps"]
    lengths = sorted((g for _, g in gaps), reverse=True)
    assert lengths[0] == pytest.approx(10e-6)    # device 1: whole window
    named = {(n, round(g * 1e9)) for n, g in gaps}
    assert ("next_batch", 2000) in named          # 4..6 us on device 0
    assert ("none", 3000) in named                # 7..10 us on device 0
    assert ("none", 1000) in named                # 0..1 us on device 0


def test_idle_share_averages_the_devices(reduced):
    rec = {"kind": "train", "trace": reduced}
    # device 0 idle 60 %, device 1 idle 100 %
    assert devtrace.idle_share(rec, "train") == pytest.approx(80.0)
    assert devtrace.idle_share(rec, "serve") is None
    assert devtrace.idle_share({"kind": "train", "trace": {}}, "train") \
        is None


def test_merged_covers_overlaps_once():
    assert devtrace.merged([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
    assert devtrace.merged([]) == []
