"""The readers of the program's own spans and counters, on a hand-built
record, on an empty one, and against a program without the record."""
import importlib.util
import pathlib
import sys

import pytest

from repro.sched import telemetry

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def reader(stem):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{stem}", METRICS / f"{stem}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# two replay episodes of 100 slots (ns), then two one-slot launches
SPANS = [
    ("engine.inputs", 0, 2_000, "engine.run", 0),
    ("engine.launch", 2_000, 3_000, "engine.run", 0),
    ("engine.wait", 3_000, 90_000, "engine.run", 0),
    ("engine.fetch", 90_000, 96_000, "engine.run", 0),
    ("engine.run", 0, 96_000, None, 0),
    ("engine.inputs", 100_000, 101_000, "engine.run", 1),
    ("engine.launch", 101_000, 102_000, "engine.run", 1),
    ("engine.wait", 102_000, 190_000, "engine.run", 1),
    ("engine.fetch", 190_000, 194_000, "engine.run", 1),
    ("engine.run", 100_000, 194_000, None, 1),
    ("engine.launch", 200_000, 203_000, None, 2),
    ("engine.launch", 210_000, 215_000, None, 3),
]
COUNTERS = {"engine.h2d_bytes": 94_400, "engine.d2h_bytes": 1_000}


@pytest.mark.parametrize("stem,slots,want", [
    ("host_launch_us", 202, (1 + 1 + 3 + 5) / 4),
    ("host_io_us", 200, ((96 - 87) + (94 - 88)) / 200),
    ("h2d_bytes", 200, 472.0),
    ("jit_misses", 200, 0),
])
def test_reader_on_a_hand_built_record(stem, slots, want):
    assert reader(stem).value(SPANS, COUNTERS, slots) == pytest.approx(want)


def test_jit_misses_counts_the_counter():
    got = reader("jit_misses").value(SPANS, {"engine.jit_misses": 3}, 200)
    assert got == 3


STEMS = ["host_launch_us", "host_io_us", "h2d_bytes", "jit_misses"]


@pytest.mark.parametrize("stem", STEMS)
def test_reader_reads_the_program_record(stem, monkeypatch):
    monkeypatch.setattr(telemetry, "records", lambda: list(SPANS))
    monkeypatch.setattr(telemetry, "counters", lambda: dict(COUNTERS))
    mod = reader(stem)
    assert mod.read({"slots": 200}) == mod.value(SPANS, COUNTERS, 200)
    # a program that keeps no record of its own (the module is missing)
    import repro.sched
    monkeypatch.delattr(repro.sched, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.sched.telemetry", None)
    assert reader(stem).read({"slots": 200}) is None


@pytest.mark.parametrize("stem", STEMS)
def test_reader_gives_nothing_on_an_empty_record(stem):
    telemetry.reset()
    assert reader(stem).read({"slots": 100}) is None
