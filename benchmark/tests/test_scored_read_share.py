"""ISSUE 42's per-layer metric, as the harness finds it: the entry and its
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
NAME = "dsa.scored_read_share"
CELL = "keye-vl-2-30b-a3b-l6.long-context-closed"


def reader():
    return frame.named_module(os.path.join(BENCH, "metrics"), NAME).read


def fake_run(after, before=None, block=256):
    def snap(c):
        if c is None:
            return {"breakdown": {}}
        return {"breakdown": {"generation": {"unit": {"counters": c}}}}

    config = {"graph": {"parameters": {"kv_block_size": block}}}
    return types.SimpleNamespace(before=snap(before), after=snap(after), config=config)


def test_the_entry_is_there_with_the_selecting_cell_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == NAME)
    assert entry["unit"] == "%" and entry["better"] == "higher"
    assert entry["source"] == "program_counter" and entry["layer"] == "kernels"
    assert entry["moves"] == "tokens_per_s" and CELL in entry["workloads"]
    cells = {w["name"] for w in manifest["workloads"]}
    moved = next(m for m in manifest["end_to_end"] if m["name"] == "tokens_per_s")
    assert set(entry["workloads"]) <= cells & set(moved.get("workloads", cells))
    assert callable(reader())
    with open(os.path.join(BENCH, "configs", "keye-vl-2-30b-a3b-l6.json")) as f:
        assert json.load(f)["graph"]["parameters"]["kv_block_size"] == 256


@pytest.mark.parametrize("contexts,blocks,share", [
    # the kernel: 7 live slots' contexts, each rounded up to its last block
    ([8748, 24644, 16900, 12000, 21000, 18000, 15000], None, 98.9685),
    # the XLA way: the window of 128 blocks for each of 8 slots
    ([8748, 24644, 16900, 12000, 21000, 18000, 15000], 8 * 128, 44.3619),
    ([256] * 8, None, 100.0),
])
def test_the_share_is_keys_scored_over_keys_read_inside_the_window(contexts, blocks, share):
    layers, steps = 6, 1600
    if blocks is None:
        blocks = sum(-(-c // 256) for c in contexts)
    before = {"moe.steps": 12, "dsa.keys_scored": 9000, "dsa.key_blocks_read": 64}
    after = {
        "moe.steps": 12 + steps,
        "dsa.keys_scored": 9000 + steps * layers * sum(contexts),
        "dsa.key_blocks_read": 64 + steps * layers * blocks,
    }
    assert reader()(fake_run(after, before)) == pytest.approx(share, abs=1e-3)


def test_a_program_without_the_counter_gives_nothing():
    """The parent of ISSUE 42 counts thirteen things and not the fourteenth."""
    old = {"moe.steps": 1612, "dsa.keys_scored": 500_000}
    for run in (fake_run(None), fake_run(old, {"moe.steps": 12}),
                fake_run({"dsa.key_blocks_read": 7, "dsa.keys_scored": 7},
                         {"dsa.key_blocks_read": 7, "dsa.keys_scored": 7})):
        assert reader()(run) is None
