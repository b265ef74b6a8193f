"""ISSUE 57's readers: the ten ``ledger.*`` entries and their files are there;
``benchmark/ledger.py`` cuts the scheduler's device ledger to the window,
leaves out the profiler's seconds, and gives nothing for a program that has
no ledger.  Presence only: no cell's list of metrics is held exactly."""

import json
import os
import types

import pytest

import frame
import ledger

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NAMES = [
    "ledger.decode_step_ms", "ledger.decode_step_ms.poisson", "ledger.decode_step_ms.chat",
    "ledger.prefill_share",
    "ledger.idle_share", "ledger.idle_share.poisson", "ledger.idle_share.chat",
    "ledger.idle_vs_trace_pts", "ledger.idle_vs_trace_pts.poisson",
    "ledger.idle_vs_trace_pts.chat",
]
COLUMNS = ["t", "busy_decode_s", "busy_prefill_s", "busy_other_s", "decode_steps",
           "idle_s", "profiler"]


def made_up(rows, traced=None, trace=None, w0=100.4, w1=105.2):
    device = {"clock_s": 200.0, "columns": COLUMNS, "seconds": rows, "traced": traced}
    unit = {"decode_read": "kernel", "device": device}
    return types.SimpleNamespace(
        w0=w0, w1=w1, trace=trace, before={},
        after={"breakdown": {"generation": {"llama:default": unit}}},
    )


def read(name, run):
    return frame.named_module(os.path.join(BENCH, "metrics"), name).read(run)


ROWS = [
    [100, 0.90, 0.00, 0.0, 100.0, {"sched:fetch": 0.10}, 0],   # before w0: cut
    [101, 0.80, 0.10, 0.0, 100.0, {"sched:fetch": 0.06, "sched:loop": 0.04}, 0],
    [102, 0.45, 0.50, 0.0, 50.0, {"sched:admit": 0.05}, 0],
    [103, 0.70, 0.00, 0.0, 50.0, {"sched:fetch": 0.30}, 1],    # a trace ran: left out
    [104, 0.10, 0.00, 0.0, 5.0, {"sched:fetch": 0.90}, 2],     # stop_trace collected
    [105, 0.50, 0.00, 0.0, 50.0, {"idle-park": 0.40, "sched:deliver": 0.10}, 0],
    [106, 0.99, 0.00, 0.0, 99.0, {"sched:fetch": 0.01}, 0],    # t >= w1: cut
]


@pytest.mark.parametrize("name", NAMES)
def test_the_entry_and_its_reader_are_there(name):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry in manifest["per_layer"][-len(NAMES):]  # appended at the end
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert (entry["source"], entry["better"]) == ("program_counter", "lower")
    assert entry["layer"] == ("model step" if "step" in name or "prefill" in name else "device")
    # a program without the ledger (the parent of ISSUE 57) gives nothing
    bare = types.SimpleNamespace(
        w0=0.0, w1=40.0, trace={"busy_s": 2.9, "window_s": 3.0}, before={},
        after={"breakdown": {"generation": {"llama:default": {"decode_read": "kernel"}}}},
    )
    assert read(name, bare) is None
    assert read(name, types.SimpleNamespace(w0=0.0, w1=40.0, trace=None, before={}, after={})) is None


def test_the_window_is_cut_at_its_edges_and_the_profilers_seconds_left_out():
    run = made_up(ROWS)
    assert [r["t"] for r in ledger.seconds(run)] == [101, 102, 105]
    assert ledger.seconds(run)[0]["idle_s"] == {"sched:fetch": 0.06, "sched:loop": 0.04}
    # the median of 8.0, 9.0 and 10.0 ms
    assert read("ledger.decode_step_ms", run) == pytest.approx(9.0)
    assert read("ledger.decode_step_ms.chat", run) == read("ledger.decode_step_ms.poisson", run)
    assert read("ledger.prefill_share", run) == pytest.approx(100 * 0.6 / 2.35)
    # idle for the host over the seconds that were not parked
    assert read("ledger.idle_share", run) == pytest.approx(100 * 0.25 / (3.0 - 0.4))
    assert read("ledger.idle_share.chat", run) == read("ledger.idle_share", run)


def test_a_second_with_no_whole_decode_step_does_not_count_for_the_step():
    rows = [[101, 0.0004, 0.9, 0.0, 0.05, {}, 0], [102, 0.8, 0.1, 0.0, 100.0, {}, 0]]
    assert read("ledger.decode_step_ms", made_up(rows)) == pytest.approx(8.0)
    assert read("ledger.decode_step_ms", made_up(rows[:1])) is None
    assert read("ledger.idle_share", made_up([])) is None


def test_the_check_against_the_trace_reads_the_traced_stretch_alone():
    traced = {"wall_s": 3.0, "running": False, "decode_steps": 300.0,
              "busy_s": {"decode": 2.7, "prefill": 0.18, "other": 0.0},
              "idle_s": {"sched:fetch": 0.05, "sched:loop": 0.01, "idle-park": 0.06}}
    trace = {"busy_s": 2.85, "window_s": 2.95}
    run = made_up(ROWS, traced, trace)
    assert read("ledger.idle_vs_trace_pts", run) == pytest.approx(
        abs(100 * 0.12 / 3.0 - 100 * (1 - 2.85 / 2.95))
    )
    assert read("ledger.idle_vs_trace_pts.poisson", made_up(ROWS, traced, None)) is None
    assert read("ledger.idle_vs_trace_pts.chat", made_up(ROWS, None, trace)) is None
