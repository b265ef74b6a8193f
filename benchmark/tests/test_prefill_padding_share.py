"""ISSUE 36's reader: ``step.prefill_padding_share`` from the model's count of
prefill rows, after less before; nothing from a program that has no such
counter; and the entry names the cell whose prompts cross a rung."""

import json
import os
import types

import pytest

import frame

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NAME = "step.prefill_padding_share"


def read(after, before=None):
    def snap(rows):
        unit = {"decode_read": "kernel"}
        if rows is not None:
            unit["prefill_rows"] = rows
        return {"breakdown": {"generation": {"cohere2_moe:default": unit}}}

    run = types.SimpleNamespace(before=snap(before), after=snap(after))
    return frame.named_module(os.path.join(BENCH, "metrics"), NAME).read(run)


def test_the_share_is_padding_over_the_rows_the_window_ran():
    # the lead-in's prompts are in both snapshots and fall out
    before = {"real": 40_000, "padded": 61_440, "by_rung": {"4096": 5, "8192": 5}}
    # a window of 30 prompts: 16 of 3,600 in the 4,096 rung, 14 of 4,580 in 6,144
    after = {"real": 40_000 + 16 * 3600 + 14 * 4580, "padded": 61_440 + 16 * 4096 + 14 * 6144,
             "by_rung": {"4096": 21, "6144": 14, "8192": 5}}
    real, padded = 16 * 3600 + 14 * 4580, 16 * 4096 + 14 * 6144
    assert read(after, before) == pytest.approx(100.0 * (padded - real) / padded)
    # no padding at all reads 0, not nothing
    assert read({"real": 512, "padded": 512, "by_rung": {"512": 1}},
                {"real": 0, "padded": 0, "by_rung": {}}) == 0.0


def test_a_program_without_the_counter_or_a_window_without_prompts_gives_nothing():
    same = {"real": 70, "padded": 96, "by_rung": {"96": 1}}
    assert read(None, None) is None  # the parent of ISSUE 36
    assert read(same, same) is None  # no prompt inside the window
    run = types.SimpleNamespace(before={}, after={"breakdown": {}})
    assert frame.named_module(os.path.join(BENCH, "metrics"), NAME).read(run) is None


def test_the_entry_names_the_long_prompt_cell_and_moves_its_metric():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    # appended: every metric the benchmark had stands before it
    assert names.index(NAME) > names.index("sched.chained_share")
    entry = manifest["per_layer"][names.index(NAME)]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "model step", "moves": "tokens_per_s",
        "workloads": ["command-a-plus-l4-ep8.long-decode-closed"],
    }
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
