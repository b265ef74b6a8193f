"""ISSUE 58's reader: ``attn.prompt_live_tile_share`` and its entry are there;
it reads the engine's sums over the prompts admitted in the window (after less
before) and gives nothing for a program that has none.  Presence only: no
cell's list of metrics is held exactly."""

import json
import os
import types

import pytest

import frame

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
NAME = "attn.prompt_live_tile_share"
CELLS = ["kimi-k2-6-l5-ep32.long-prompt-closed", "command-a-plus-l4-ep8.long-decode-closed"]


def snapshot(admitted=None, plans=None):
    tile_plans = dict(plans or {"S12288:Sk12288:512x512:wNone": {"stepped": 300, "live": 300, "masked": 24}})
    if admitted is not None:
        tile_plans["admitted"] = {"stepped": admitted[0], "live": admitted[1]}
    unit = {"decode_read": "kernel", "programs": {"compiles": 9, "tile_plans": tile_plans}}
    return {"breakdown": {"generation": {"kimi_k2:default": unit}}}


def read(before, after):
    run = types.SimpleNamespace(before=before, after=after, w0=0.0, w1=40.0, trace=None)
    return frame.named_module(os.path.join(BENCH, "metrics"), NAME).read(run)


def test_the_entry_is_appended_for_the_two_cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = [m["name"] for m in manifest["per_layer"]]
    # after everything ISSUE 57 left (a later entry may follow it)
    assert names.index(NAME) > names.index("ledger.idle_vs_trace_pts.chat")
    assert manifest["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "tokens_per_s", "workloads": CELLS,
    }
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == "tokens_per_s"]
    assert set(CELLS) <= set(moved["workloads"])
    assert os.path.exists(os.path.join(BENCH, "metrics", NAME + ".py"))


def test_it_reads_the_windows_own_admissions():
    # 31 prompts before the window (the lead-in), 72 more inside it
    before, after = snapshot((7_000, 5_500)), snapshot((7_000 + 14_488, 5_500 + 11_023))
    assert read(before, after) == pytest.approx(100 * 11_023 / 14_488)
    # a first snapshot from before anything was admitted
    assert read(snapshot((0, 0)), snapshot((300, 153))) == pytest.approx(51.0)
    assert read({}, snapshot((300, 153))) == pytest.approx(51.0)


@pytest.mark.parametrize("before,after", [
    (snapshot(), snapshot()),                       # the parent of ISSUE 58: plans, no sums
    ({}, {}),
    ({}, {"breakdown": {"generation": {"llama:default": {"programs": {"tile_plans": {}}}}}}),
    (snapshot((300, 153)), snapshot((300, 153))),   # nothing admitted in the window
])
def test_a_program_without_the_sums_gives_nothing(before, after):
    assert read(before, after) is None
