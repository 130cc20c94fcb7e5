"""The reduction of the program's own spans and scopes, on a small
hand-built trace: one device, the main thread with the harness's spans and
the serve worker's line with the program's, and the per-layer readings of
records made by hand."""
import pytest

import perfbench_testkit  # noqa: F401  (puts the repository on sys.path)
from perfbench import devtrace, progtrace

# times in ps; the window runs 0..20 us. Worker line: poll 0..2, file 2..3
# holding assemble 2.5..3, compute 3..8 holding dispatch 3..4 and readback
# 4..8, scatter 8..9, poll 9..21 (clipped at 20), poll 22..23 (outside).
# Device: a forward message op 4..7, a backward node_update op 7..7.5, an
# unscoped copy 5..6, an optimizer op 19..21 (clipped at 20); the step
# module runs 4..7.5 and again 19..22, a third of it in the window. The
# device is idle 0..4 and 7.5..19.
SPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 500000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 19000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 4000000 duration_ps: 3500000 }
    events { metadata_id: 5 offset_ps: 19000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion(x)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[8] fusion(y)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[8] copy(x)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = f32[8] fusion(z)" } }
  event_metadata { key: 5 value { id: 5 name: "jit_step(1)" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 8000000 }
  }
  lines { id: 2 name: "python3" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 11 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 12 offset_ps: 2500000 duration_ps: 500000 }
    events { metadata_id: 13 offset_ps: 3000000 duration_ps: 5000000 }
    events { metadata_id: 14 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 15 offset_ps: 4000000 duration_ps: 4000000 }
    events { metadata_id: 16 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 10 offset_ps: 9000000 duration_ps: 12000000 }
    events { metadata_id: 10 offset_ps: 22000000 duration_ps: 1000000 }
    events { metadata_id: 17 offset_ps: 9500000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "result_wait" } }
  event_metadata { key: 10 value { id: 10 name: "serve.poll" } }
  event_metadata { key: 11 value { id: 11 name: "serve.file" } }
  event_metadata { key: 12 value { id: 12 name: "serve.assemble" } }
  event_metadata { key: 13 value { id: 13 name: "serve.compute" } }
  event_metadata { key: 14 value { id: 14 name: "serve.dispatch" } }
  event_metadata { key: 15 value { id: 15 name: "serve.readback" } }
  event_metadata { key: 16 value { id: 16 name: "serve.scatter" } }
  event_metadata { key: 17 value { id: 17 name: "PjitFunction(f)" } }
}
"""
HLO = """
HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="scatter-add"}
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b), metadata={op_name="add"}
}

%fused_computation.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %reshape.9 = f32[8]{0} reshape(%p), metadata={op_name="jit(step)/transpose(jvp(vmap(egnn)))/layer3/message/split"}
  ROOT %scatter.9 = f32[8]{0} scatter(%p, %reshape.9), to_apply=%region_0
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%c1, metadata={op_name="jit(step)/jvp(egnn)/layer0/message/dot_general" source_file="gnn.py" source_line=1}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%c2, metadata={op_name="jit(step)/transpose(jvp(vmap(egnn)))/layer1/node_update/mul"}
  %copy.3 = f32[8]{0} copy(%x)
  %fusion.9 = f32[8]{0} fusion(%x), kind=kCustom, calls=%fused_computation.9
  ROOT %fusion.4 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%c3, metadata={op_name="jit(step)/optimizer/sub"}
}
"""


@pytest.fixture(scope="module")
def space():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(SPACE)


@pytest.fixture(scope="module")
def reduced(space):
    tr = devtrace.reduce_space(space)
    tr.update(progtrace.reduce_program(space,
                                       progtrace.hlo_op_paths(HLO)))
    return tr


def test_hlo_op_paths_read_the_op_name_of_each_instruction():
    paths = progtrace.hlo_op_paths(HLO)
    assert paths["fusion.1"].endswith("layer0/message/dot_general")
    assert paths["fusion.4"] == "jit(step)/optimizer/sub"   # a ROOT line
    assert "copy.3" not in paths and "x" not in paths


def test_a_fusion_without_metadata_takes_its_computations_path():
    """XLA leaves some fusions (the sorted scatter of a gather's backward)
    without metadata: the path comes from the computation it calls,
    skipping paths that name no scope (the scatter's reducer)."""
    paths = progtrace.hlo_op_paths(HLO)
    assert progtrace.scope_of(paths["fusion.9"]) == "message"
    assert paths["add.0"] == "add"


@pytest.mark.parametrize("path, scope", [
    ("jit(step)/jvp(egnn)/layer0/message/dot_general", "message"),
    ("jit(step)/transpose(jvp(vmap(egnn)))/layer3/node_update/mul",
     "node_update"),
    ("jit(step)/transpose(jvp(vmap(heads)))/dot_general", "heads"),
    ("jit(step)/vmap(loss)/reduce_sum", "loss"),
    ("jit(step)/optimizer/sqrt", "optimizer"),
    ("jit(forward)/egnn/embed/gather", "embed"),
    ("jit(step)/jvp(egnn)/layer0/messages/add", None),
    ("jit(step)/add", None),
    (None, None),
])
def test_scope_is_a_path_component_whatever_wraps_it(path, scope):
    assert progtrace.scope_of(path) == scope


def test_host_spans_are_counted_and_clipped_per_line_role(reduced):
    worker = reduced["host_spans"]["serve_worker"]
    assert worker["serve.poll"]["count"] == 2        # the third is outside
    assert worker["serve.poll"]["s"] == pytest.approx(13e-6)
    assert worker["serve.readback"]["s"] == pytest.approx(4e-6)
    assert worker["serve.dispatch"]["count"] == 1
    # the main thread carries only harness spans: none of them is counted
    assert reduced["host_spans"]["main"] == {}
    assert reduced["span_cover_s"]["serve_worker"] == pytest.approx(20e-6)
    assert reduced["window_s"] == pytest.approx(20e-6)


def test_device_scopes_sum_forward_and_backward_by_scope(reduced):
    scopes = reduced["device_scopes"]
    assert scopes["message"] == pytest.approx(3e-6)
    # the backward op inside transpose(jvp(vmap(...))) lands in its scope
    assert scopes["node_update"] == pytest.approx(0.5e-6)
    assert scopes["optimizer"] == pytest.approx(1e-6)  # clipped at 20 us
    assert scopes["unscoped"] == pytest.approx(1e-6)


def test_device_scopes_need_name_paths(space):
    assert "device_scopes" not in progtrace.reduce_program(space)


def test_step_executions_count_a_clipped_run_by_its_fraction(reduced):
    assert reduced["step_module"] == "jit_step(1)"
    assert reduced["step_executions"] == pytest.approx(4 / 3)


def test_idle_goes_to_the_worker_span_over_the_main_threads(reduced):
    idle = {k: round(v * 1e9) for k, v in reduced["idle_by_span"].items()}
    # result_wait on the main thread covers 12..19 us: it takes none of it
    assert idle == {"serve.poll": 12000, "serve.file": 500,
                    "serve.assemble": 500, "serve.dispatch": 1000,
                    "serve.readback": 500, "serve.scatter": 1000}
    # the harness's reduction of the same trace still names the longer
    # gap by the main thread's span at its midpoint
    assert ["result_wait", pytest.approx(11.5e-6)] in reduced["idle_gaps"]


def test_idle_under_no_program_span_is_none():
    idle = progtrace.idle_by_span([(0, 10)], [(2, 4, "data.draw")])
    assert {k: round(v * 1e9) for k, v in idle.items()} == \
        {"none": 8, "data.draw": 2}


# -- the per-layer readings ------------------------------------------------

def _serve(reduced):
    return {"kind": "serve", "trace": reduced, "window_compilations": 0}


def _train(scopes=None, produce=True):
    tr = {"window_s": 2.0, "step_executions": 4.0,
          "device_scopes": scopes or {"message": 0.8, "node_update": 0.2,
                                      "heads": 0.08, "loss": 0.02,
                                      "optimizer": 0.01, "unscoped": 0.05},
          "host_spans": {"data_producer": {
              "data.draw": {"count": 5, "s": 0.010},
              "data.place": {"count": 5, "s": 0.005}}} if produce else {}}
    return {"kind": "train", "trace": tr, "window_compilations": 1}


SERVE_READERS = ("serve_worker_busy_share", "device_idle_in_host.serve",
                 "window_compilations.serve")
TRAIN_READERS = ("train_device_ms.message", "train_device_ms.node_update",
                 "train_device_ms.heads", "train_input_produce_ms",
                 "window_compilations.train")


def test_every_reader_is_of_one_kind():
    assert set(progtrace.READERS) == set(SERVE_READERS + TRAIN_READERS)


@pytest.mark.parametrize("name, value", [
    ("serve_worker_busy_share", 35.0),       # 100 * (20 - 13) / 20
    ("device_idle_in_host.serve", 15.0),     # (0.5+0.5+1+1) / 20
    ("window_compilations.serve", 0.0),
])
def test_serve_readers(reduced, name, value):
    read = progtrace.READERS[name]
    assert read(_serve(reduced)) == pytest.approx(value)
    assert read(_train()) is None


@pytest.mark.parametrize("name, value", [
    ("train_device_ms.message", 200.0),      # 1e3 * 0.8 / 4
    ("train_device_ms.node_update", 50.0),
    ("train_device_ms.heads", 25.0),         # heads and loss
    ("train_input_produce_ms", 3.0),         # 1e3 * 0.015 / 5
    ("window_compilations.train", 1.0),
])
def test_train_readers(reduced, name, value):
    read = progtrace.READERS[name]
    assert read(_train()) == pytest.approx(value)
    assert read(_serve(reduced)) is None


@pytest.mark.parametrize("name", SERVE_READERS + TRAIN_READERS)
def test_readers_find_nothing_in_an_untraced_run(name):
    for kind in ("serve", "train"):
        rec = {"kind": kind, "trace": None}
        assert progtrace.READERS[name](rec) is None


def test_train_readers_need_scopes_and_producer_spans():
    rec = _train(produce=False)
    assert progtrace.READERS["train_input_produce_ms"](rec) is None
    del rec["trace"]["device_scopes"]
    assert progtrace.READERS["train_device_ms.message"](rec) is None
