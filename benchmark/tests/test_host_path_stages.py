"""ISSUE 40's per-layer metrics of the host path, as the harness finds them:
each entry and its reader by name, and the readers on hand-made pairs of
``/stats/summary`` snapshots.  Presence, not exact lists or last place
(PERF.md §7)."""

import bisect
import json
import math
import os
import types

import pytest

import frame
import stats

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

# metric -> (the stage it reads, the end-to-end metric it moves, its layer)
MEDIANS = {
    "sched.slot_wait_ms_p50": ("slot-wait", "ttft_ms_p95", "generation scheduler"),
    "sched.slot_wait_ms_p50.poisson": ("slot-wait", "ttft_ms_p50", "generation scheduler"),
    "sched.admit_round_ms_p50": ("admit-round", "ttft_ms_p95", "generation scheduler"),
    "sched.admit_round_ms_p50.poisson": ("admit-round", "ttft_ms_p50", "generation scheduler"),
    "sched.sync_point_ms_p50": ("sync-point", "tpot_ms_p95", "generation scheduler"),
    "sched.sync_point_ms_p50.poisson": ("sync-point", "tpot_ms_p50", "generation scheduler"),
    "engine.ingress_ms_p50": ("ingress", "ttft_ms_p95", "engine ingress"),
    "engine.ingress_ms_p50.poisson": ("ingress", "ttft_ms_p50", "engine ingress"),
    "engine.first_write_ms_p50": ("first-write", "ttft_ms_p95", "engine ingress"),
    "engine.first_write_ms_p50.poisson": ("first-write", "ttft_ms_p50", "engine ingress"),
}
PER_BLOCK = "sched.sync_ms_per_block"
ALL = {**MEDIANS, PER_BLOCK: ("sync-point", "tokens_per_s", "generation scheduler")}


def reader(name):
    return frame.named_module(os.path.join(BENCH, "metrics"), name).read


def hist(samples_s) -> list[int]:
    """Bucket counts on the benchmark's copy of the program's grid."""
    h = [0] * stats.HIST_SLOTS
    for x in samples_s:
        h[bisect.bisect_left(stats.BUCKET_EDGES, x)] += 1
    return h


def middle(x: float) -> float:
    """The geometric middle of the bucket that holds ``x`` seconds."""
    i = bisect.bisect_left(stats.BUCKET_EDGES, x)
    return math.sqrt(stats.BUCKET_EDGES[i - 1] * stats.BUCKET_EDGES[i])


def fake_run(after_hist, before_hist=None, after_bounds=None, before_bounds=None):
    def snap(h, b):
        unit = {} if b is None else {"block_boundaries": b}
        return {"stage_hist": h or {}, "breakdown": {"generation": {"unit": unit}}}

    return types.SimpleNamespace(
        before=snap(before_hist, before_bounds), after=snap(after_hist, after_bounds),
        stats=stats,
    )


@pytest.mark.parametrize("name", sorted(ALL))
def test_the_entry_is_there_with_a_reader_and_cells_that_report_what_it_moves(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    _, moves, layer = ALL[name]
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert entry["moves"] == moves and entry["layer"] == layer
    moved = next(m for m in manifest["end_to_end"] if m["name"] == moves)
    cells = {w["name"] for w in manifest["workloads"]}
    assert entry["workloads"]
    assert set(entry["workloads"]) <= cells & set(moved.get("workloads", cells))
    assert callable(reader(name))


@pytest.mark.parametrize("name", sorted(MEDIANS))
def test_a_median_is_the_stage_s_inside_the_window(name):
    stage = MEDIANS[name][0]
    before = {stage: hist([0.5] * 9), "ttft": hist([0.07] * 3)}
    after = {stage: hist([0.5] * 9 + [0.002, 0.012, 0.012, 0.012, 0.3]),
             "ttft": hist([0.07] * 9)}
    got = reader(name)(fake_run(after, before))
    assert got == pytest.approx(middle(0.012) * 1e3)
    # and another stage's samples are not this one's
    other = {"ttft": hist([0.07] * 9)}
    assert reader(name)(fake_run(other, other)) is None


@pytest.mark.parametrize("name", sorted(ALL))
def test_a_program_without_the_stage_gives_nothing(name):
    """The parent of ISSUE 40 records ``ttft`` and ``device-step`` and none
    of the five; a window in which the stage recorded nothing is the same
    for a median."""
    bounds = {"chained_early": 600, "chained_due": 0, "chained_late": 0,
              "idle": 0, "sync": {"admission": 40}}
    old = {"ttft": hist([0.07] * 9), "device-step": hist([0.09] * 640)}
    assert reader(name)(fake_run(old, None, bounds, None)) is None
    assert reader(name)(fake_run({}, {})) is None
    if name in MEDIANS:
        stage = MEDIANS[name][0]
        same = {stage: hist([0.01] * 4)}
        assert reader(name)(fake_run(same, same, bounds, bounds)) is None


def test_sync_ms_per_block_is_the_sync_points_time_over_all_boundaries():
    """decode-closed's shape: 16 boundaries a wave, one of them a sync point
    of 32 prefills."""
    b0 = {"chained_early": 30, "chained_due": 0, "chained_late": 0, "idle": 1,
          "sync": {"admission": 2}}
    b1 = {"chained_early": 30 + 15 * 20, "chained_due": 3, "chained_late": 1,
          "idle": 1, "sync": {"admission": 2 + 20, "carry-dirty": 1}}
    blocks = 15 * 20 + 3 + 1 + 20 + 1
    before = {"sync-point": hist([0.4, 0.4])}
    after = {"sync-point": hist([0.4, 0.4] + [0.31] * 20 + [0.004])}
    got = reader(PER_BLOCK)(fake_run(after, before, b1, b0))
    assert got == pytest.approx((20 * middle(0.31) + middle(0.004)) * 1e3 / blocks)
    # the stage is there and the window met no sync point: nothing was paid
    assert reader(PER_BLOCK)(fake_run(before, before, b1, b0)) == 0.0
    # no boundary in the window: no block to pay
    assert reader(PER_BLOCK)(fake_run(after, before, b0, b0)) is None
