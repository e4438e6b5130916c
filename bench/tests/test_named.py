"""The reduction by the program's names, on a synthesized trace with
hand-counted answers."""

import pytest
from jax.profiler import ProfileData

import named

# Device ops (ns), each op name a stat of the op's event metadata as on the
# chip: flash_fwd 1,000-3,000; an adamw fusion 3,000-5,000 (its op name a
# reference to a stat metadata, its operand a parameter named for mlp); an
# lm_loss backward fusion 5,000-6,000; a gap of 100,000; flash_dq
# 106,000-110,000 and flash_dkv 110,000-113,000 inside a loop 106,000-113,500
# whose op name holds the attention scope; a gap of 80,000; an mlp fusion
# 193,500-196,000 whose source file is attention.py; a fusion 196,000-197,000
# whose op name is a quoted parameter path with "mlp".  The window span
# covers 500-200,500.
#
# Step-loop thread ("python", holds bench/window): train/step 4,000-150,000
# with train/log 50,000-110,000 inside; train/step 150,000-199,000 with
# train/realize 150,000-160,000 and bench/pull 151,000-156,000 inside.  A
# second thread, also named "python", holds train/realize 40,000-70,000 and
# prefetch/produce 140,000-170,000, which must name no gap.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 105000000 duration_ps: 4000000 }
    events { metadata_id: 5 offset_ps: 109000000 duration_ps: 3000000 }
    events { metadata_id: 6 offset_ps: 105000000 duration_ps: 7500000 }
    events { metadata_id: 7 offset_ps: 192500000 duration_ps: 2500000 }
    events { metadata_id: 8 offset_ps: 195000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%flash_fwd.1 = (f32[8]{0}, f32[8]{0}) custom-call(f32[8]{0} %p), custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 9 str_value: "jit(train_step)/jvp(checkpoint)/attention/flash_fwd/pallas_call:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %state__params____mlp____w__.1), kind=kLoop"
    stats { metadata_id: 9 ref_value: 11 } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput"
    stats { metadata_id: 9 str_value: "jit(train_step)/transpose(jvp(lm_loss))/dot_general:" } } }
  event_metadata { key: 4 value { id: 4 name: "%flash_dq.2 = f32[8]{0} custom-call(f32[8]{0} %p), custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 9 str_value: "jit(train_step)/transpose(jvp())/while/body/checkpoint/attention/flash_dq/pallas_call:" } } }
  event_metadata { key: 5 value { id: 5 name: "%flash_dkv.3 = (f32[8]{0}, f32[8]{0}) custom-call(f32[8]{0} %p), custom_call_target=\\"tpu_custom_call\\""
    stats { metadata_id: 9 str_value: "jit(train_step)/transpose(jvp())/while/body/checkpoint/attention/flash_dkv/pallas_call:" } } }
  event_metadata { key: 6 value { id: 6 name: "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"
    stats { metadata_id: 9 str_value: "jit(train_step)/transpose(jvp())/attention/while:" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput"
    stats { metadata_id: 10 str_value: "/src/repro/models/attention.py:12" }
    stats { metadata_id: 9 str_value: "jit(train_step)/transpose(jvp())/while/body/checkpoint/mlp/dot_general:" } } }
  event_metadata { key: 8 value { id: 8 name: "%fusion.10 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    stats { metadata_id: 9 str_value: "state['opt']['m']['mlp']" }
    stats { metadata_id: 10 str_value: "/src/repro/train/optimizer.py:54" } } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
  stat_metadata { key: 10 value { id: 10 name: "source" } }
  stat_metadata { key: 11 value { id: 11 name: "jit(train_step)/adamw/mul:" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 146000000 }
    events { metadata_id: 3 offset_ps: 49500000 duration_ps: 60000000 }
    events { metadata_id: 2 offset_ps: 149500000 duration_ps: 49000000 }
    events { metadata_id: 4 offset_ps: 149500000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 150500000 duration_ps: 5000000 }
  }
  lines { id: 2 name: "python" timestamp_ns: 500
    events { metadata_id: 4 offset_ps: 39500000 duration_ps: 30000000 }
    events { metadata_id: 6 offset_ps: 139500000 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
  event_metadata { key: 2 value { id: 2 name: "train/step" } }
  event_metadata { key: 3 value { id: 3 name: "train/log" } }
  event_metadata { key: 4 value { id: 4 name: "train/realize" } }
  event_metadata { key: 5 value { id: 5 name: "bench/pull" } }
  event_metadata { key: 6 value { id: 6 name: "prefetch/produce" } }
}
"""


# the labels the benchmark's readers ask for
READ = {"kernels": ("flash_fwd", "flash_dq", "flash_dkv"), "scopes": ("attention", "mlp", "lm_loss", "adamw")}


def _reduced(text=TRACE):
    buf = ProfileData.text_proto_to_serialized_xspace(text)
    return named.reduce(
        *named.from_profile(ProfileData.from_serialized_xspace(buf)), named.device_op_names(buf)
    )


def test_op_names_come_from_the_event_metadata():
    ops = named.device_op_names(ProfileData.text_proto_to_serialized_xspace(TRACE))
    assert len(ops) == 8
    assert ops["%fusion.7 = f32[8]{0} fusion(f32[8]{0} %state__params____mlp____w__.1), kind=kLoop"] == (
        "jit(train_step)/adamw/mul:")  # by reference
    assert ops["%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput"] == (
        "jit(train_step)/transpose(jvp(lm_loss))/dot_general:")


def test_device_time_by_kernel_name_and_scope():
    red = _reduced()
    assert red["window_s"] == pytest.approx(200_000e-9)
    assert named.labelled_s(red, **READ) == pytest.approx({
        "flash_fwd": 2_000e-9, "flash_dq": 4_000e-9, "flash_dkv": 3_000e-9,
        "adamw": 2_000e-9, "lm_loss": 1_000e-9, "mlp": 2_500e-9,
        "attention": 9_000e-9,  # the three kernels, not the loop that holds two
    })


def test_gaps_go_to_the_loop_threads_innermost_span():
    red = _reduced()
    gaps = dict(red["idle_by_span"])
    assert gaps["train/log"] == pytest.approx(100_000e-9)  # not the other thread's realize
    assert gaps["bench/pull"] == pytest.approx(80_000e-9)  # not prefetch/produce
    assert gaps[named.SHORT] == pytest.approx(4_000e-9)  # 500 before, 3,500 after
    assert set(gaps) == {"train/log", "bench/pull", named.SHORT}
    assert red["realize_idle_s"] == pytest.approx(80_000e-9)


def test_without_the_window_span_the_loop_is_the_train_step_thread():
    text = TRACE.replace('name: "bench/window"', 'name: "other"')
    red = _reduced(text)
    # the window falls back to the device ops, 1,000-197,000; gaps as before
    assert red["window_s"] == pytest.approx(196_000e-9)
    gaps = dict(red["idle_by_span"])
    assert gaps["train/log"] == pytest.approx(100_000e-9)
    assert gaps["bench/pull"] == pytest.approx(80_000e-9)


def test_a_trace_without_program_names_reads_none():
    text = TRACE
    for name in ("flash_fwd", "flash_dq", "flash_dkv", "adamw", "lm_loss", "mlp", "attention",
                 "train/realize", "train/log", "train/step"):
        text = text.replace(name, "plain")
    red = _reduced(text)
    assert named.labelled_s(red, **READ) == {}
    assert red["realize_idle_s"] is None
    assert dict(red["idle_by_span"])["bench/pull"] == pytest.approx(80_000e-9)


def test_any_kernel_or_scope_label_gets_its_time(tmp_path):
    # the dK/dV kernel renamed expert_gmm, the mlp fusion's scope renamed moe
    text = TRACE.replace("flash_dkv", "expert_gmm").replace("/mlp/", "/moe/")
    red = _reduced(text)
    assert named.labelled_s(red, kernels=("expert_gmm",), scopes=("moe",)) == pytest.approx(
        {"expert_gmm": 3_000e-9, "moe": 2_500e-9})
    assert named.labelled_s(red, **READ)["flash_dq"] == pytest.approx(4_000e-9)  # the others keep theirs
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    run = {"trace": {"steps": 2, "path": str(path)}}
    assert named.kernel_ms(run, "expert_gmm") == pytest.approx(3_000e-6 / 2)
    assert named.scope_ms(run, "moe") == pytest.approx(2_500e-6 / 2)
    assert named.scope_ms(run, "moe", "adamw") == pytest.approx(4_500e-6 / 2)
    assert named.kernel_ms(run, "moe") is None  # a scope is no kernel name
    assert named.kernel_ms(run, "no_such_kernel") is None
    assert named.kernel_ms(run, "flash_fwd") == pytest.approx(2_000e-6 / 2)
    assert named.scope_ms(run, "attention") == pytest.approx(9_000e-6 / 2)


def test_the_trace_is_walked_once_for_every_label(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    run = {"trace": {"steps": 2, "path": str(path)}}
    named._reduce_file.cache_clear()
    readings = [named.kernel_ms(run, "flash_fwd"), named.kernel_ms(run, "flash_dq", "flash_dkv"),
                named.scope_ms(run, "lm_loss"), named.scope_ms(run, "adamw"), named.for_run(run)]
    assert all(r is not None for r in readings)
    assert named._reduce_file.cache_info().misses == 1
