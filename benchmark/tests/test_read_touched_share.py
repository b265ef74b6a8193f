"""ISSUE 39's per-layer metric, as the harness finds it: the entry and its
reader by name, and the reader on recorded pairs of ``/stats/summary``
counters.  Presence, not exact lists or last place (PERF.md §7)."""

import json
import os
import types

import pytest

import frame

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = "moe.read_touched_share"
CELLS = {"command-a-plus-l4-ep8.long-decode-closed", "keye-vl-2-30b-a3b-l6.long-context-closed"}


def reader():
    return frame.named_module(os.path.join(BENCH, "metrics"), NAME).read


def fake_run(after, before=None):
    def snap(c):
        if c is None:
            return {"breakdown": {}}
        return {"breakdown": {"generation": {"unit": {"counters": c}}}}

    return types.SimpleNamespace(before=snap(before), after=snap(after))


def test_the_entry_is_there_with_both_expert_cells_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry["unit"] == "%" and entry["better"] == "higher"
    assert entry["source"] == "program_counter" and entry["layer"] == "model step"
    assert entry["moves"] == "tokens_per_s" and CELLS <= set(entry["workloads"])
    cells = {w["name"] for w in manifest["workloads"]}
    moved = next(m for m in manifest["end_to_end"] if m["name"] == "tokens_per_s")
    assert set(entry["workloads"]) <= cells & set(moved.get("workloads", cells))
    assert callable(reader())


@pytest.mark.parametrize("touched,read,share", [
    (6 * 45, 6 * 45, 100.0),   # the kernel: what was touched was read
    (6 * 45, 6 * 128, 35.15625),  # Keye-VL-2.0's dense step: all 128 read
    (4 * 14, 4 * 16, 87.5),    # Command A+: 14 of 16 held
])
def test_the_share_is_touched_over_read_inside_the_window(touched, read, share):
    before = {"moe.steps": 12, "moe.experts_touched": 1100, "moe.experts_read": 1536}
    steps = 1600
    after = {"moe.steps": 12 + steps, "moe.experts_touched": 1100 + steps * touched,
             "moe.experts_read": 1536 + steps * read}
    assert reader()(fake_run(after, before)) == pytest.approx(share)


def test_a_program_without_the_counter_gives_nothing():
    """The parent of ISSUE 39 counts eight things and not the ninth."""
    old = {"moe.steps": 1612, "moe.experts_touched": 500_000}
    for run in (fake_run(None), fake_run(old, {"moe.steps": 12}),
                fake_run({"moe.experts_read": 7, "moe.experts_touched": 7},
                         {"moe.experts_read": 7, "moe.experts_touched": 7})):
        assert reader()(run) is None
