"""The reader of ``dp_launches.replay``: the traced window's kernel count
per slot, on the recorded trace of ``test_trace_reduce``, on a window with
no kernel, and on an empty window."""
import dataclasses
import importlib.util
import pathlib

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce
from bench.tests.test_trace_reduce import TRACE

PATH = pathlib.Path(__file__).resolve().parents[1] / "metrics" / "dp_launches.py"


def reader():
    spec = importlib.util.spec_from_file_location("bench_metric_dp_launches",
                                                  PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(TRACE))


def test_launches_per_slot_from_the_trace(reduced):
    # two Mosaic custom calls in the window; the fusion that reads the
    # kernel's output is not a launch
    assert reader()({"trace": reduced, "slots": 2}) == pytest.approx(1.0)
    assert reader()({"trace": reduced, "slots": 1}) == pytest.approx(2.0)


def test_no_launch_or_no_slot_reads_nothing(reduced):
    none = dataclasses.replace(reduced, kernel_count=0, kernel_s=0.0)
    assert reader()({"trace": none, "slots": 4}) is None
    assert reader()({"trace": reduced, "slots": 0}) is None
