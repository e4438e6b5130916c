"""The trace reduction on a synthesized trace with hand-counted answers."""

import pytest
from jax.profiler import ProfileData

import xplane

# Device ops (ns): a fusion 1,000-3,000, a Pallas kernel 4,000-5,000, a gap
# of 99,000 while the host is in bench/pull, a second kernel 104,000-106,000
# inside a loop 104,000-106,500; one program execution starts at 1,000.  The
# window span covers 500-110,500.
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 103000000 duration_ps: 2000000 }
    events { metadata_id: 5 offset_ps: 103000000 duration_ps: 2500000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 105000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kOutput" } }
  event_metadata { key: 2 value { id: 2 name: "%checkpoint.2 = (f32[8]{0}, f32[8]{0}) custom-call(f32[8]{0} %p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%closed_call.7 = f32[8]{0} custom-call(f32[8]{0} %q), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 5 value { id: 5 name: "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b" } }
  event_metadata { key: 4 value { id: 4 name: "jit_train_step(123)" } }
  stat_metadata { key: 9 value { id: 9 name: "long_name" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 110000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 99000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
  event_metadata { key: 2 value { id: 2 name: "bench/pull" } }
}
"""


def test_reduce_counts_busy_idle_kernels_and_modules():
    planes = xplane.from_profile(ProfileData.from_text_proto(TRACE))
    red = xplane.reduce(planes, kernels={"flash": r'custom_call_target="tpu_custom_call"'},
                        module="train_step")
    assert red["window_s"] == pytest.approx(110_000e-9)
    assert red["busy_s"] == pytest.approx(5_500e-9)
    assert red["kernel_s"]["flash"] == pytest.approx(3_000e-9)
    assert red["modules"] == 1
    gaps = dict(red["idle_gaps"])
    assert gaps["bench/pull"] == pytest.approx(99_000e-9)
    # 500 before the first op, 1,000 between ops, 4,000 after the last
    assert gaps["device: between ops (< 50 us)"] == pytest.approx(5_500e-9)
    # by kind, the loop (which holds the second kernel) left out
    assert dict(red["device_ops"]) == pytest.approx(
        {"custom-call:tpu_custom_call": 3_000e-9, "fusion:kOutput": 2_000e-9})


def test_window_falls_back_to_the_pull_and_sync_spans():
    text = TRACE.replace('name: "bench/window"', 'name: "bench/sync"')
    red = xplane.reduce(xplane.from_profile(ProfileData.from_text_proto(text)))
    # first pull at 4,500 to the end of the sync span at 110,500
    assert red["window_s"] == pytest.approx(106_000e-9)
    text = TRACE.replace('name: "bench/window"', 'name: "other"').replace('"bench/pull"', '"other2"')
    red = xplane.reduce(xplane.from_profile(ProfileData.from_text_proto(text)))
    assert red["window_s"] == pytest.approx(105_500e-9)  # first to last device op


def test_a_kernel_family_counts_only_the_mosaic_calls_it_names():
    import harness

    text = TRACE.replace("%checkpoint.2 =", "%flash_fwd.2 =")
    planes = xplane.from_profile(ProfileData.from_text_proto(text))
    families = {"flash": "flash_fwd|flash_dq|flash_dkv", "gmm": "expert_gmm"}
    red = xplane.reduce(planes, kernels=harness.kernel_patterns(families))
    # the renamed kernel counts; %closed_call.7, a Mosaic call of no family, does not
    assert red["kernel_s"] == pytest.approx({"flash": 1_000e-9, "gmm": 0.0})
    assert xplane.reduce(planes, kernels=harness.kernel_patterns({}))["kernel_s"] == {}
