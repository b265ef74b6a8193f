"""ISSUE 38's cell, as the harness finds it: the configuration, its reference
kind and the traffic mix by name; the configuration file against the
published config it cites; the costs against the issue's bytes; the two
readers on a recorded pair of ``/stats/summary`` snapshots.  Presence, not
exact lists or last place (PERF.md §7 (9))."""

import json
import os
import types

import pytest

import costs_keye_vl2 as ck
import frame
import peaks
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "keye-vl-2-30b-a3b-l6.long-context-closed"
CONFIG = "keye-vl-2-30b-a3b-l6"


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load("BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return load(f"benchmark/configs/{CONFIG}.json")


@pytest.fixture(scope="module")
def graph(config):
    return config["graph"]["parameters"]


def reader(name):
    return frame.named_module(os.path.join(BENCH, "metrics"), name).read


# ------------------------------------------------------------ found by name


def test_the_cell_its_configuration_kind_judge_and_mix_are_found_by_name(manifest, config):
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    mix = load(f"benchmark/traffic/{cell['traffic']}.json")
    assert (mix["loop"], mix["route"], mix["clients"], mix["pool"]) == ("closed", "stream", 12, 64)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 8192, "max": 24576}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 768}
    assert (mix["lead_in_s"], mix["drain_s"], mix["trace_slice_s"]) == (15.0, 40.0, 3.0)
    assert mix["temperature"] == 0.0
    kind = frame.named_module("kinds", config["reference"]["kind"])
    assert callable(kind.check) and kind.JUDGE == "token_logits_by_context"
    assert callable(frame.named_module("judges", kind.JUDGE).judge)
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]
            if CELL in m.get("workloads", [])}
    assert mine >= {"tokens_per_s", "step.decode_ms", "sched.chained_share",
                    "step.prefill_share",
                    "moe.tokens_per_held_expert", "moe.load_imbalance",
                    "dsa.selected_share", "kernel.dsa_decode_hbm_roofline"}
    for name in mine:
        assert callable(reader(name))
    new = {m["name"]: m for m in manifest["per_layer"]}
    assert new["dsa.selected_share"]["better"] == "lower"
    assert new["dsa.selected_share"]["source"] == "program_counter"
    assert new["kernel.dsa_decode_hbm_roofline"]["source"] == "device_trace"
    for name in ("dsa.selected_share", "kernel.dsa_decode_hbm_roofline"):
        assert new[name]["moves"] == "tokens_per_s" and new[name]["unit"] == "%"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_file_holds_every_published_number_but_the_reduced(manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    reduced = set(entry["reduced"])
    assert reduced == set(config["reduced"]) == {"num_hidden_layers", "max_position_embeddings"}
    catalog = os.path.join("/opt/skills/guides/model-configs/architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
        assert config["published"] == row["config"] and config["source"] == row["source_url"]
    for key, value in config["published"].items():
        if key in reduced:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["max_position_embeddings"]) == (6, 32768)
    for key in ("assumed", "deployment", "note", "reference", "model"):
        assert config[key]
    assert "8 pipeline stages of 6 whole layers" in config["deployment"]
    assert {"qk_norm", "indexer_input", "indexer_norm_rope", "intermediate_size"} <= set(config["assumed"])


def test_the_graph_runs_the_published_widths(config, graph):
    pub = config["published"]
    sa = pub["sa_config"]
    assert graph["family"] == "keye_vl2"
    assert (graph["hidden"], graph["n_heads"], graph["n_kv_heads"], graph["head_dim"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"], pub["head_dim"])
    assert (graph["ffn"], graph["n_experts"], graph["experts_per_tok"]) == (
        pub["moe_intermediate_size"], pub["num_experts"], pub["num_experts_per_tok"])
    assert (graph["index_heads"], graph["index_dim"], graph["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert sa["indexer_num_kv_heads"] == 1 and "experts_held" not in graph
    assert (graph["rope_theta"], graph["norm_eps"], graph["vocab_size"]) == (
        pub["rope_theta"], pub["rms_norm_eps"], pub["vocab_size"])
    assert graph["n_layers"] == config["num_hidden_layers"]
    assert graph["max_seq"] == config["max_position_embeddings"]
    # the pool holds what the mix can ask of every slot
    mix = load("benchmark/traffic/long-context-closed.json")
    need = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"]) // graph["kv_block_size"])
    assert graph["kv_blocks"] == 1 + graph["n_slots"] * need == 793
    # the rehearsal's probes (64 + 32 tokens) cross its topk
    assert load(f"benchmark/rehearsal/{CONFIG}.json")["graph"]["parameters"]["index_topk"] < 64


# -------------------------------------------------------------------- costs


def test_the_bytes_are_the_issues(graph):
    assert ck.attention_params(graph) == 2 * 2048 * 4096 + 2 * 2048 * 512 == 18_874_368
    assert ck.indexer_params(graph) == 2048 * 1024 + 2048 * 64 + 2048 * 16 == 2_260_992
    assert ck.router_params(graph) == 262_144
    assert ck.expert_params(graph) == 3 * 2048 * 768 == 4_718_592
    assert round(ck.layer_params(graph) / 1e6, 1) == 625.4
    assert round(2 * ck.layer_params(graph) / 1e9, 3) == 1.251
    assert 7.50 <= 2 * 6 * ck.layer_params(graph) / 1e9 <= 7.51  # the issue: 6 x 1.251
    assert round(2 * 2 * 151936 * 2048 / 1e9, 3) == 1.245
    assert round(2 * ck.model_params(graph) / 1e9, 2) == 8.75
    assert ck.kv_row_bytes(graph) == 2048 and ck.index_key_bytes(graph) == 128
    assert ck.pool_bytes_per_token(graph) == 6 * 2176 == 13_056
    pool = graph["kv_blocks"] * graph["kv_block_size"] * ck.pool_bytes_per_token(graph)
    assert round(pool / 1e9, 2) == 2.65


def test_a_decode_steps_bytes_follow_the_counters(graph):
    dense = 2 * (6 * ck.dense_layer_params(graph) + ck.head_params(graph))
    assert ck.decode_step_bytes(graph, 0.0, 0.0, 0.0) == dense
    assert ck.decode_step_bytes(graph, 52.0 * 6, 0.0, 0.0) - dense == 312 * 2 * 4_718_592
    assert ck.decode_step_bytes(graph, 0.0, 1000.0, 100.0) - dense == 128_000 + 204_800
    # every expert, 8 slots at 16,896 tokens: the step the program runs today
    every = ck.decode_step_bytes(graph, 128.0 * 6, 6 * 8 * 16896.0, 6 * 8 * 2048.0)
    assert 8.3e9 < every < 8.5e9
    assert ck.selected_pairs(graph, 1000) == 1000 * 1001 / 2
    assert ck.selected_pairs(graph, 24576) == 2048 * 2049 / 2 + (24576 - 2048) * 2048
    whole = ck.prefill_flops(graph, 24576)
    assert 48e12 < whole < 56e12  # projections and experts 17, index 3.7, tiled attention 30
    chosen = ck.prefill_flops(graph, 24576, ck.selected_pairs(graph, 24576))
    assert 25e12 < chosen < 29e12


# ------------------------------------------------------------------ readers

# a pair of /stats/summary snapshots as the engine gives them (the counters
# of a window of 1,600 decode steps on six layers, eight slots at 16,896)
BEFORE = {"moe.pairs_routed": 4608, "moe.pairs_held": 4608, "moe.experts_touched": 1100,
          "moe.max_tokens_on_expert": 40, "moe.steps": 12, "moe.prefill_pairs_routed": 12288,
          "moe.prefill_pairs_held": 12288, "moe.prefill_tokens": 256,
          "dsa.keys_scored": 55296, "dsa.keys_selected": 55296,
          "dsa.prefill_keys_scored": 0, "dsa.prefill_keys_selected": 0}
STEPS = 1600
AFTER = {**BEFORE,
         "moe.steps": 12 + STEPS,
         "moe.pairs_routed": 4608 + STEPS * 6 * 64, "moe.pairs_held": 4608 + STEPS * 6 * 64,
         "moe.experts_touched": 1100 + STEPS * 6 * 52,
         "moe.max_tokens_on_expert": 40 + STEPS * 6 * 3,
         "dsa.keys_scored": 55296 + STEPS * 6 * 8 * 16896,
         "dsa.keys_selected": 55296 + STEPS * 6 * 8 * 2048}


def fake_run(config, after, before=None, programs=None):
    def snap(c):
        return {"breakdown": {"generation": {"keye_vl2:default": {"counters": c}}}}

    return types.SimpleNamespace(
        config=config, mix=load("benchmark/traffic/long-context-closed.json"),
        before=snap(before) if before is not None else {"breakdown": {}},
        after=snap(after) if after is not None else {"breakdown": {}},
        trace=None if programs is None else {"programs": programs, "busy_s": 3.0},
        peaks=peaks.peaks_of("TPU v5 lite"), chips=1, traffic=traffic,
    )


def test_the_selected_share_is_selected_over_scored(config):
    run = fake_run(config, AFTER, BEFORE)
    assert reader("dsa.selected_share")(run) == pytest.approx(100 * 2048 / 16896)
    # the older readers work on this family's counters unchanged
    assert reader("moe.tokens_per_held_expert")(run) == pytest.approx(0.5)
    assert reader("moe.load_imbalance")(run) == pytest.approx(6.0)


def test_the_roofline_share_counts_touched_experts_and_counted_keys(config, graph):
    programs = [{"label": "decode_k:k16:w32768[kernel]", "device_s": 16 * 0.020}] * 10 + [
        {"label": "prefill:b24576[kernel]", "device_s": 0.9}]
    run = fake_run(config, AFTER, BEFORE, programs)
    share = reader("kernel.dsa_decode_hbm_roofline")(run)
    need = ck.decode_step_bytes(graph, 6 * 52.0, 6 * 8 * 16896.0, 6 * 8 * 2048.0)
    assert share == pytest.approx(100 * need / 819e9 / 0.020)
    assert 25 < share < 35
    # a program that reads every expert and every key at the roofline's own
    # speed reads under 100 %
    every = ck.decode_step_bytes(graph, 6 * 128.0, 6 * 8 * 16896.0, 6 * 8 * 16896.0)
    fast = [{"label": "decode_k:k16:w32768[kernel]", "device_s": 16 * every / 819e9}]
    assert reader("kernel.dsa_decode_hbm_roofline")(fake_run(config, AFTER, BEFORE, fast)) < 100


def test_a_program_without_the_counters_gives_the_readers_nothing(config):
    """The parent of ISSUE 38 cannot run the cell; a program with no
    ``dsa.*`` counters (another family's snapshot) gives None and raises
    nothing."""
    programs = [{"label": "decode_k:k16:w32768[kernel]", "device_s": 0.32}]
    moe_only = {k: v for k, v in AFTER.items() if k.startswith("moe.")}
    for run in (fake_run(config, None, None, programs),
                fake_run(config, moe_only, None, programs),
                fake_run(config, BEFORE, BEFORE, programs),
                fake_run(config, AFTER, BEFORE, None)):
        assert reader("kernel.dsa_decode_hbm_roofline")(run) is None
    for run in (fake_run(config, None), fake_run(config, moe_only), fake_run(config, BEFORE, BEFORE)):
        assert reader("dsa.selected_share")(run) is None


# -------------------------------------------------------------------- judge


# the controls as read on the chip, the nearest to the limits of three seeds
# (my chip runs, PR 38): index scores in bfloat16; the selection switched off
CONTROLS = [
    {"selection_swaps_max": 20, "attention_rel_err_max": 0.109,
     "decode_read_rel_err_max": 0.058},
    {"selection_swaps_max": 10240, "attention_rel_err_max": 0.906,
     "decode_read_rel_err_max": 0.887},
]
SOUND = {"selection_swaps_max": 0, "projection_rel_err_max": 0.004,
         "attention_rel_err_max": 0.008, "decode_read_rel_err_max": 0.006}


def test_the_judge_holds_each_set_of_numbers_to_its_own_limits(config):
    """Positions that attend every key are held as ``token_logits`` holds
    them; positions that select by their agreement alone; the selection's
    own numbers wherever the configuration states their limits; a set with
    no position is not judged, and nothing judged is not a pass."""
    judge = frame.named_module("judges", "token_logits_by_context")
    limits = config["reference"]
    assert "selecting_logit_margin" not in limits  # no limit above its control
    assert limits["argmax_agree_min"] > limits["selecting_argmax_agree_min"]
    assert limits["selection_probe_tokens"] >= 4 * 2048
    dense = {"positions_dense": 128, "logit_deficit_max_dense": 0.29,
             "argmax_agree_share_dense": 0.875, "positions_selecting": 0, **SOUND}
    assert judge.judge(dense, limits)
    assert [r[0] for r in judge.compared(dense, limits)] == [
        "logit_deficit_max_dense", "argmax_agree_share_dense",
        "selection_swaps_max", "projection_rel_err_max", "attention_rel_err_max",
        "decode_read_rel_err_max"]
    assert not judge.judge({**dense, "logit_deficit_max_dense": 1.0}, limits)
    # a run whose kind did not look at the selection is not a pass
    with pytest.raises(KeyError):
        judge.judge({k: v for k, v in dense.items() if k not in SOUND}, limits)
    long = {"positions_dense": 0, "positions_selecting": 128,
            "logit_deficit_max_selecting": 1.45, "argmax_agree_share_selecting": 0.49, **SOUND}
    assert judge.judge(long, limits)
    assert not judge.judge({**long, "argmax_agree_share_selecting": 0.23}, limits)
    # the controls' readings on the chip (my chip runs, PR 38): each is
    # refused, the index scores in bfloat16 by the keys swapped
    for control in CONTROLS:
        assert not judge.judge({**dense, **control}, limits)
        assert not frame.all_hold(judge.selection_rows({**SOUND, **control}, limits))
    without = {k: v for k, v in limits.items() if not k.startswith("selection_")}
    assert not judge.judge({"positions_dense": 0, "positions_selecting": 0}, without)


def test_the_selection_probe_holds_on_the_rehearsal_and_refuses_both_controls():
    """The kind's ``mechanism`` at the rehearsal's sizes on the CPU: the
    served graph inside the rehearsal's limits, the index scores in
    bfloat16 and the selection switched off outside them."""
    import dataclasses

    rehearsal = load(f"benchmark/rehearsal/{CONFIG}.json")
    graph, limits = rehearsal["graph"]["parameters"], rehearsal["reference"]
    kind = frame.named_module("kinds", limits["kind"])
    judge = frame.named_module("judges", kind.JUDGE)
    cfg, head, layers, _ = kind.model(graph, 7, 1)
    n = limits["selection_probe_tokens"]
    assert n > graph["index_topk"] + 2 * kind.JUDGED_ROWS
    found = kind.mechanism(cfg, graph, head, layers, 7, n)
    assert frame.all_hold(judge.selection_rows(found, limits)), found
    assert found["selection_rows_judged"] == 2 * kind.JUDGED_ROWS * graph["n_layers"]
    for control in ({"index_dtype": "bfloat16"}, {"select": "off"}):
        other = kind.mechanism(dataclasses.replace(cfg, **control), graph, head, layers, 7, n)
        assert not frame.all_hold(judge.selection_rows(other, limits)), (control, other)


def test_the_kind_splits_the_positions_at_topk():
    kind = frame.named_module("kinds", "keye_vl2_decoder")
    assert kind.summary([], 0, "_dense") == {"positions_dense": 0}
    got = kind.summary([0.0, 0.5, 0.25, 0.0], 2, "_selecting")
    assert got == {"positions_selecting": 4, "argmax_agree_share_selecting": 0.5,
                   "logit_deficit_max_selecting": 0.5, "logit_deficit_p99_selecting": 0.5}
