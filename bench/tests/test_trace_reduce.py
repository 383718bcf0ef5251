"""The trace reduction and the readers on a small recorded trace, whose
planes, lines and names follow a TPU v5e profile of the engine."""
import importlib.util
import pathlib

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"

# times in ns; the host's bench.call span sets the window [1000, 11000]
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 0 duration_ps: 12000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 10500000 duration_ps: 1500000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.12 = f32[12]{0} fusion(f32[12]{0} %p.1), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%dp_forward_pallas.10 = (f32[1,920,128]) custom-call(s32[33]{0} %copy-done.8), custom_call_target=\\"tpu_custom_call\\", frontend_attributes={kernel_metadata={}}" } }
  event_metadata { key: 3 value { id: 3 name: "%select_or_fusion.2 = s32[8,44032,128] fusion(s32[44032,128] %pallas_call.11, pred[] %compare.181), kind=kLoop" } }
  event_metadata { key: 4 value { id: 4 name: "%closed_call.88 = (f32[44032,128]) custom-call(s32[8]{0} %fusion.119), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 9 value { id: 9 name: "jit_run_scan" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 2500000 } }
  lines { id: 2 name: "worker" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 11500000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.call" } }
  event_metadata { key: 2 value { id: 2 name: "bench.fetch" } }
  event_metadata { key: 3 value { id: 3 name: "bench.gen" } }
  event_metadata { key: 4 value { id: 4 name: "bench.check" } }
}
"""


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(TRACE))


def test_window_busy_and_kernel(reduced):
    assert reduced.devices == 1
    assert reduced.window_s == pytest.approx(10e-6)
    # fusion clipped to [1000, 2000], kernel ∪ select_or = [2500, 5000],
    # second kernel [7000, 8000], last fusion clipped to [10500, 11000]
    assert reduced.busy_s == pytest.approx(5e-6)
    assert reduced.kernel_s == pytest.approx(3e-6)
    assert reduced.kernel_busy_s == pytest.approx(3e-6)
    assert reduced.kernel_count == 2


def test_idle_gaps_named_by_host_span(reduced):
    gaps = sorted(reduced.idle_gaps, key=lambda g: -g[1])
    assert gaps == [("bench.fetch", pytest.approx(2.5e-6)),
                    ("bench.call", pytest.approx(2e-6)),
                    ("bench.call", pytest.approx(0.5e-6))]
    ops = dict(reduced.top_ops)
    assert ops[next(k for k in ops if k.startswith("%dp_forward_pallas.10"))] \
        == pytest.approx(2e-6)
    assert ops[next(k for k in ops if k.startswith("%fusion.12"))] \
        == pytest.approx(1.5e-6)
    bd = reduced.breakdown()
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert len(bd["device_ops"]) == 4


def _reader(stem):
    spec = importlib.util.spec_from_file_location(stem, METRICS / f"{stem}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_readers(reduced):
    ctx = {"trace": reduced, "slots": 4, "compile_s": 1.5,
           "device_kind": "TPU v5 lite", "sizes": {"S": 919, "C": 12, "E": 33}}
    assert _reader("device_idle_share").read(ctx) == pytest.approx(50.0)
    assert _reader("dp_kernel_us").read(ctx) == pytest.approx(0.75)
    assert _reader("slot_ops_us").read(ctx) == pytest.approx(0.5)
    assert _reader("compile_s").read(ctx) == 1.5
    roof = _reader("dp_kernel_roofline")
    want = roof.bytes_min(919, 12, 33) / 819e9 / 0.75e-6 * 100
    assert roof.read(ctx) == pytest.approx(want)
    assert roof.bytes_min(919, 12, 33) == 4 * (2 * 919 * 12 + 33 * 12) \
        + 33 * 919 * 12 / 8
    with pytest.raises(KeyError):
        roof.read(dict(ctx, device_kind="TPU v4"))
    empty = dict(ctx, slots=0)
    assert _reader("dp_kernel_us").read(empty) is None


def test_no_kernel_found_reads_nothing(reduced):
    """A trace in which no kernel event is found cannot be split into
    kernel and pipeline: the readers that split it return nothing."""
    import dataclasses

    blind = dataclasses.replace(reduced, kernel_s=0.0, kernel_busy_s=0.0,
                                kernel_count=0)
    ctx = {"trace": blind, "slots": 4, "compile_s": 1.5,
           "device_kind": "TPU v5 lite", "sizes": {"S": 919, "C": 12, "E": 33}}
    for stem in ("dp_kernel_us", "dp_kernel_roofline", "slot_ops_us"):
        assert _reader(stem).read(ctx) is None, stem
    assert _reader("device_idle_share").read(ctx) == pytest.approx(50.0)
