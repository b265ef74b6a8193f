"""ISSUE 31's reader: ``sched.chained_share`` from the scheduler's count of
block boundaries by outcome, after less before; nothing from a program that
has no such counter; and the entry names the three closed cells."""

import json
import os
import types

import pytest

import frame

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NAME = "sched.chained_share"


def read(after, before=None):
    def snap(b):
        unit = {"decode_read": "kernel"}
        if b is not None:
            unit["block_boundaries"] = b
        return {"breakdown": {"generation": {"llama:default": unit}}}

    run = types.SimpleNamespace(before=snap(before), after=snap(after))
    return frame.named_module(os.path.join(BENCH, "metrics"), NAME).read(run)


def test_the_share_is_chained_blocks_over_all_boundaries_of_the_window():
    before = {"chained_early": 30, "chained_late": 2, "idle": 1, "sync": {"admission": 7}}
    # a window of 10 waves of 16 blocks: 15 chained early, the 16th admits
    after = {"chained_early": 180, "chained_late": 2, "idle": 1,
             "sync": {"admission": 16, "carry-dirty": 1}}
    assert read(after, before) == pytest.approx(100.0 * 150 / 160)
    # late chains count as chained; an idle boundary and a sync point do not
    assert read({"chained_early": 1, "chained_late": 2, "idle": 1, "sync": {"admission": 4}},
                {"chained_early": 0, "chained_late": 0, "idle": 0, "sync": {}}) == pytest.approx(37.5)


def test_a_program_without_the_counter_or_a_window_without_blocks_gives_nothing():
    same = {"chained_early": 5, "chained_late": 0, "idle": 0, "sync": {"admission": 1}}
    assert read(None, None) is None  # the parent of ISSUE 31
    assert read(same, same) is None  # no block inside the window
    run = types.SimpleNamespace(before={}, after={"breakdown": {}})
    assert frame.named_module(os.path.join(BENCH, "metrics"), NAME).read(run) is None


def test_the_entry_names_the_closed_cells_and_moves_their_metric():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == manifest["per_layer"][-1]  # appended, nothing before it moved
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert entry["workloads"] == moved["workloads"]
    assert (entry["layer"], entry["source"], entry["better"], entry["unit"]) == (
        "generation scheduler", "program_counter", "higher", "%")
