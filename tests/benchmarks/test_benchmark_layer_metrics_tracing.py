"""The per-layer readers PR 25 listed, on the made-up run object of
test_benchmark_layer_metrics.py with one lane more: a program that marks its
ticks' phases, counts its compilations and records the request's stages.

These are the rows that file's `RUN`, `EMPTY` and `WANT` would have gained.
They live here because a PR that changes the program adds files to the
benchmark and edits none; tests/conftest.py joins `WANT` to that file's table
at collection, so that its check of the listed metrics still sees every pin."""

import copy

import pytest

from test_benchmark_layer_metrics import EMPTY, RUN as PARENT_RUN
from test_benchmark_layer_metrics import _reader, _span

# worker_2's ticks have worker_1's durations and widths, so no reading of the
# older metrics moves. `host_phases` is the trace's host plane as
# lib/host_phases.py reduces it.
RUN = copy.deepcopy(PARENT_RUN)
RUN["stats_before"]["worker_2"] = {"compile": {"count": 7, "seconds": 1.5}}
RUN["stats_after"]["worker_2"] = {"compile": {"count": 9, "seconds": 2.5}}
RUN["spans"]["worker_2"] = [
    _span("mixed_step", 150000, width=1, form_us=4000.0, dispatch_us=1000.0,
          wait_us=140000.0, apply_us=5000.0),
    _span("mixed_step", 160000, width=1, form_us=4000.0, dispatch_us=2000.0,
          wait_us=150000.0, apply_us=4000.0, gap_us=9000.0),
    _span("mixed_step", 170000, width=1, form_us=5000.0, dispatch_us=1000.0,
          wait_us=158000.0, apply_us=6000.0, gap_us=12000.0),
    _span("mixed_step", 400000, width=256, form_us=6000.0,
          dispatch_us=3000.0, wait_us=387000.0, apply_us=4000.0,
          gap_us=10000.0),
    _span("prefill", 500000, prompt_len=600, chunks=3, starved_ticks=0,
          starved_us=0),
    _span("prefill", 9000000, prompt_len=900, chunks=4, starved_ticks=17,
          starved_us=8400000),
    _span("slot_wait", 700, parked=False),
    _span("slot_wait", 300, parked=False),
    _span("slot_wait", 90000, parked=True),
    _span("generate_stream", 900000, ttft_us=480000),
    _span("generate_stream", 800000, ttft_us=520000),
    _span("generate_stream", 100000, segment="error"),
]
RUN["host_phases"] = {"idle_host_s": 0.4}

WANT = {
    "sched.host_gap_ms": 10.0,
    "step.decode_device_ms": 152.0,
    "step.prefill_device_ms": 390.0,
    "sched.budget_wait_ms": 4200.0,
    "lane.slot_wait_ms": 0.7,
    "lane.ttft_p50_ms": 480.0,
    "step.compiles": 2,
    "device.idle_host": 16.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _reader(name)(RUN) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_with_nothing_to_read_returns_nothing(name):
    assert _reader(name)(EMPTY) is None


@pytest.mark.parametrize("name", sorted(set(WANT) - {"device.idle_host"}))
def test_reader_returns_nothing_on_a_program_without_the_marks(name):
    """The parent's run object: the older spans and counters only.
    (`device.idle_host` would go and look for a trace file on the disk; its
    case is test_benchmark_host_phases.py's.)"""
    assert _reader(name)(PARENT_RUN) is None


def test_the_second_lane_moves_no_older_reading():
    import test_benchmark_layer_metrics as older
    for name in older.WANT:
        if name in WANT:
            continue
        assert _reader(name)(RUN) == pytest.approx(older.WANT[name]), name


def test_a_program_that_does_not_count_compilations_reads_nothing_not_zero():
    warm = dict(RUN, stats_after={"worker_2": {"compile": {"count": 7}}},
                stats_before={"worker_2": {"compile": {"count": 7}}})
    assert _reader("step.compiles")(warm) == 0
    older = dict(RUN, stats_after={"worker_1": {"mixed": {}}},
                 stats_before={"worker_1": {"mixed": {}}})
    assert _reader("step.compiles")(older) is None


def test_idle_host_reads_nothing_from_a_trace_without_annotations():
    assert _reader("device.idle_host")(dict(RUN, host_phases={})) is None
