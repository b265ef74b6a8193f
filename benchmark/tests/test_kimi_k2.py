"""ISSUE 43's cell, as the harness finds it: the configuration, its reference
kind, its judge and the traffic mix by name; the configuration file against
the published config it cites; the costs against the issue's bytes; the four
readers on a recorded pair of ``/stats/summary`` snapshots.  Presence, not
exact lists or last place (PERF.md §7 (9))."""

import json
import os
import types

import pytest

import costs_kimi_k2 as ck
import frame
import peaks
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "kimi-k2-6-l5-ep32.long-prompt-closed"
CONFIG = "kimi-k2-6-l5-ep32"
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size", "max_position_embeddings"}


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
    assert (mix["loop"], mix["route"], mix["clients"], mix["pool"]) == ("closed", "stream", 48, 256)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 4096, "max": 12288}
    assert mix["output_len"] == {"dist": "uniform", "min": 256, "max": 768}
    assert (mix["lead_in_s"], mix["drain_s"], mix["trace_slice_s"]) == (20.0, 40.0, 3.0)
    assert mix["temperature"] == 0.0
    kind = frame.named_module("kinds", config["reference"]["kind"])
    assert callable(kind.check) and kind.JUDGE == "token_logits_and_parts"
    assert callable(frame.named_module("judges", kind.JUDGE).judge)
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]
            if CELL in m.get("workloads", [])}
    assert mine >= {"tokens_per_s", "step.decode_ms", "step.prefill_share",
                    "sched.chained_share", "sched.sync_ms_per_block",
                    "moe.load_imbalance", "moe.read_touched_share",
                    "kernel.mla_decode_hbm_roofline", "mla.cache_read_share",
                    "kernel.mla_prefill_mxu_roofline", "mla.needed_read_share"}
    # its reader divides by every layer; four of five have experts here
    assert "moe.tokens_per_held_expert" not in mine
    for name in mine:
        assert callable(reader(name))
    new = {m["name"]: m for m in manifest["per_layer"]}
    assert new["kernel.mla_decode_hbm_roofline"]["source"] == "device_trace"
    assert new["kernel.mla_decode_hbm_roofline"]["layer"] == "kernels"
    assert new["mla.cache_read_share"]["source"] == "program_counter"
    assert new["mla.cache_read_share"]["layer"] == "model step"
    assert new["kernel.mla_prefill_mxu_roofline"]["source"] == "device_trace"
    assert new["kernel.mla_prefill_mxu_roofline"]["layer"] == "kernels"
    assert new["mla.needed_read_share"]["source"] == "program_counter"
    assert new["mla.needed_read_share"]["layer"] == "kernels"
    for name in ("kernel.mla_decode_hbm_roofline", "mla.cache_read_share",
                 "kernel.mla_prefill_mxu_roofline", "mla.needed_read_share"):
        assert new[name]["moves"] == "tokens_per_s" and new[name]["unit"] == "%"
        assert new[name]["better"] == "higher" and CELL in new[name]["workloads"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) >= 7


def test_the_file_holds_every_published_number_but_the_reduced(manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    catalog = os.path.join("/opt/skills/guides/model-configs/architectures.jsonl")
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-K2.6")
        assert config["published"] == row["config"] and config["source"] == row["source_url"]
    for key, value in config["published"].items():
        if key in REDUCED:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"], config["vocab_size"],
            config["max_position_embeddings"]) == (5, 12, 20480, 16384)
    for key in ("assumed", "deployment", "note", "reference", "model"):
        assert config[key]
    for said in ("32 chips share each layer", "12 a chip", "over 8 of them", "pipeline stages"):
        assert said in config["deployment"], said
    assert {"rotary_pairs", "latent_norms", "router_bias", "training_keys"} <= set(config["assumed"])
    assert "0.67" in config["note"] and "21.3" in config["note"]


def test_the_graph_runs_the_published_widths(config, graph):
    pub = config["published"]
    rope = pub["rope_scaling"]
    assert graph["family"] == "kimi_k2"
    assert (graph["hidden"], graph["n_heads"], graph["q_lora_rank"], graph["kv_lora_rank"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["q_lora_rank"], pub["kv_lora_rank"])
    assert (graph["qk_nope_dim"], graph["qk_rope_dim"], graph["v_head_dim"]) == (
        pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"])
    assert (graph["ffn_dense"], graph["ffn"], graph["n_experts"], graph["experts_per_tok"]) == (
        pub["intermediate_size"], pub["moe_intermediate_size"], pub["n_routed_experts"],
        pub["num_experts_per_tok"])
    assert (graph["n_shared_experts"], graph["routed_scale"], graph["n_dense_layers"]) == (
        pub["n_shared_experts"], pub["routed_scaling_factor"], pub["first_k_dense_replace"])
    assert (graph["rope_theta"], graph["norm_eps"]) == (pub["rope_theta"], pub["rms_norm_eps"])
    assert (graph["rope_factor"], graph["rope_original_max"], graph["rope_beta_fast"],
            graph["rope_beta_slow"], graph["rope_mscale"], graph["rope_mscale_all_dim"]) == (
        rope["factor"], rope["original_max_position_embeddings"], rope["beta_fast"],
        rope["beta_slow"], rope["mscale"], rope["mscale_all_dim"])
    assert graph["experts_held"] == config["experts_held"] == "0:12"
    assert ck.held(graph) == config["n_routed_experts"]
    assert graph["vocab_size"] == config["vocab_size"]
    assert graph["n_layers"] == config["num_hidden_layers"]
    assert graph["max_seq"] == config["max_position_embeddings"]
    for control in ("decode_rope", "softmax_mscale", "decode_score_dtype",
                    "prompt_score_dtype"):
        assert control not in graph  # never served
    # the pool holds what the mix can ask of every slot
    mix = load("benchmark/traffic/long-prompt-closed.json")
    need = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"]) // graph["kv_block_size"])
    assert graph["kv_blocks"] == 1 + graph["n_slots"] * need == 1633
    # the rehearsal's probes (64 + 32 tokens) pass its YaRN original length
    assert load(f"benchmark/rehearsal/{CONFIG}.json")["graph"]["parameters"]["rope_original_max"] < 64


# -------------------------------------------------------------------- costs


def test_the_bytes_are_the_issues(graph):
    assert round(ck.attention_params(graph) / 1e6, 1) == 101.1
    assert ck.expert_params(graph) == 3 * 7168 * 2048 == 44_040_192
    assert round(ck.router_params(graph) / 1e6, 2) == 2.75
    assert round(ck.expert_layer_params_unrouted(graph) / 1e6, 2) == 147.93
    assert round(ck.expert_layer_params(graph) / 1e6, 1) == 676.4
    assert round(2 * ck.expert_layer_params(graph) / 1e9, 3) == 1.353
    assert round(ck.dense_layer_params(graph) / 1e6, 1) == 497.5
    assert round(2 * ck.dense_layer_params(graph) / 1e9, 3) == 0.995
    assert round(2 * 2 * 20480 * 7168 / 1e9, 3) == 0.587
    assert round(ck.share_params(graph) / 1e9, 3) == 3.497
    assert round(2 * ck.share_params(graph) / 1e9, 2) == 6.99
    assert ck.latent_row_bytes(graph) == 1152 and ck.by_head_row_bytes(graph) == 40_960
    assert ck.pool_bytes_per_token(graph) == 5 * 1152 == 5760
    assert round(ck.pool_bytes(graph) / 1e9, 2) == 2.41
    assert round(32 * 13056 * 5 * ck.by_head_row_bytes(graph) / 1e9, 1) == 85.6
    assert ck.absorb_params(graph) * 2 == 16_777_216  # 16.8 MB of Wkvb a layer


def test_a_decode_steps_bytes_follow_the_counters(graph):
    fixed = 2 * (
        ck.dense_layer_params(graph) + 4 * ck.expert_layer_params_unrouted(graph)
        + ck.head_params(graph)
    )
    assert ck.decode_step_bytes(graph, 0.0, 0.0) == fixed
    assert ck.decode_step_bytes(graph, 24.0, 0.0) - fixed == 24 * 2 * 44_040_192
    assert ck.decode_step_bytes(graph, 0.0, 1000.0) - fixed == 1_152_000
    # the touched half of the held experts, 32 slots at 8,700 tokens: the
    # issue's 4.5 GB of weights and 1.6 GB of latents
    assert 4.4e9 < ck.decode_weight_bytes(graph, 24.0) < 4.7e9
    assert 1.5e9 < ck.latent_read_bytes(graph, 5 * 32 * 8700.0) < 1.7e9
    # the issue's prompts: 18 / 25 / 43 TFLOP in the 6,144 / 8,192 / 12,288 rungs
    got = [round(ck.prefill_flops(graph, t) / 1e12) for t in (6144, 8192, 12288)]
    assert got == [18, 25, 43]


# ------------------------------------------------------------------ readers

# a pair of /stats/summary snapshots as the engine gives them (the counters
# of a window of 1,200 decode steps: four expert layers, 32 slots at 8,700)
BEFORE = {"moe.pairs_routed": 4096, "moe.pairs_held": 130, "moe.experts_touched": 90,
          "moe.max_tokens_on_expert": 30, "moe.steps": 16, "moe.prefill_pairs_routed": 8192,
          "moe.prefill_pairs_held": 260, "moe.prefill_tokens": 256, "moe.experts_read": 90,
          "mla.rows_read": 40960, "mla.prefill_rows_expanded": 1280, "mla.rows_live": 40000}
STEPS = 1200
AFTER = {**BEFORE,
         "moe.steps": 16 + STEPS,
         "moe.pairs_routed": 4096 + STEPS * 4 * 256, "moe.pairs_held": 130 + STEPS * 4 * 8,
         "moe.experts_touched": 90 + STEPS * 4 * 6, "moe.experts_read": 90 + STEPS * 4 * 6,
         "moe.max_tokens_on_expert": 30 + STEPS * 4 * 3,
         # the read awaits whole blocks; the positions say what was needed
         "mla.rows_read": 40960 + STEPS * 5 * 32 * 8704,
         "mla.rows_live": 40000 + STEPS * 5 * 32 * 8600,
         # 72 prompts: 580,000 real tokens in 696,320 rows of their rungs
         "moe.prefill_tokens": 256 + 580_000,
         "mla.prefill_rows_expanded": 1280 + 5 * 696_320,
         "moe.prefill_pairs_held": 260 + 4 * 580_000 * 0.26}


def fake_run(config, after, before=None, programs=None):
    def snap(c):
        return {"breakdown": {"generation": {"kimi_k2:default": {"counters": c}}}}

    return types.SimpleNamespace(
        config=config, mix=load("benchmark/traffic/long-prompt-closed.json"),
        before=snap(before) if before is not None else {"breakdown": {}},
        after=snap(after) if after is not None else {"breakdown": {}},
        trace=None if programs is None else {"programs": programs, "busy_s": 3.0},
        peaks=peaks.peaks_of("TPU v5 lite"), chips=1, traffic=traffic,
    )


def test_the_cache_read_share_is_the_latents_over_what_the_step_reads(config, graph):
    run = fake_run(config, AFTER, BEFORE)
    latents = 1152 * 5 * 32 * 8600.0
    weights = ck.decode_weight_bytes(graph, 24.0)
    share = reader("mla.cache_read_share")(run)
    assert share == pytest.approx(100 * latents / (latents + weights))
    assert 15 < share < 40
    # the older readers work on this family's counters unchanged
    assert reader("moe.read_touched_share")(run) == pytest.approx(100.0)
    assert reader("moe.load_imbalance")(run) == pytest.approx(3 / (8 / 12))
    # a window of short contexts has stopped working the mechanism
    short = {**AFTER, "mla.rows_live": 40000 + STEPS * 5 * 32 * 96}
    assert reader("mla.cache_read_share")(fake_run(config, short, BEFORE)) < 1


def test_the_roofline_share_counts_touched_experts_and_needed_rows_once(config, graph):
    programs = [{"label": "decode_k:k16:w16384[kernel]", "device_s": 16 * 0.0085}] * 10 + [
        {"label": "prefill:b12288[kernel]", "device_s": 0.5}]
    run = fake_run(config, AFTER, BEFORE, programs)
    share = reader("kernel.mla_decode_hbm_roofline")(run)
    need = ck.decode_step_bytes(graph, 24.0, 5 * 32 * 8600.0)
    assert share == pytest.approx(100 * need / 819e9 / 0.0085)
    assert 80 < share < 95
    # a block cut by the slice's edge (its 16 steps for a part of its time)
    # does not move the share: the median of the blocks, not their mean
    cut = programs + [{"label": "decode_k:k16:w16384[kernel]", "device_s": 0.03}]
    assert reader("kernel.mla_decode_hbm_roofline")(
        fake_run(config, AFTER, BEFORE, cut)) == pytest.approx(share)
    # a program that reads every held expert and every row twice at the
    # roofline's own speed reads under 100 %
    every = ck.decode_step_bytes(graph, 48.0, 2 * 5 * 32 * 8704.0)
    fast = [{"label": "decode_k:k16:w16384[kernel]", "device_s": 16 * every / 819e9}]
    assert reader("kernel.mla_decode_hbm_roofline")(fake_run(config, AFTER, BEFORE, fast)) < 100
    # a read that awaits rows it does not need raises its own count, not its
    # share; mla.needed_read_share says so
    assert reader("mla.needed_read_share")(run) == pytest.approx(100 * 8600 / 8704)
    greedy = {**AFTER, "mla.rows_read": 2 * AFTER["mla.rows_read"]}
    twice = fake_run(config, greedy, BEFORE, programs)
    assert reader("kernel.mla_decode_hbm_roofline")(twice) == pytest.approx(share)
    assert 49 < reader("mla.needed_read_share")(twice) < 50


def test_the_prompts_share_of_the_mxu_counts_real_rows_and_held_pairs(config, graph):
    programs = [
        {"label": "prefill:b12288[kernel]", "device_s": 0.556},
        {"label": "prefill:b12288[kernel]", "device_s": 0.556},
        {"label": "prefill:b6144[kernel]", "device_s": 0.211},
        {"label": "decode_k:k16:w16384[kernel]", "device_s": 16 * 0.0085},
        {"label": "suffix:b256:p4096", "device_s": 9.0},  # left out
    ]
    run = fake_run(config, AFTER, BEFORE, programs)
    share = reader("kernel.mla_prefill_mxu_roofline")(run)
    real = 580_000 / 696_320
    # the median program's share: here a 12,288 rung's
    assert share == pytest.approx(100 * ck.prefill_flops(graph, 12288 * real, 0.26) / 0.556 / 197e12)
    assert 20 < share < 60
    # a program cut by the slice's edge (its whole prompt for a part of its
    # time) does not move it
    cut = programs + [{"label": "prefill:b12288[kernel]", "device_s": 0.1}]
    assert reader("kernel.mla_prefill_mxu_roofline")(
        fake_run(config, AFTER, BEFORE, cut)) == pytest.approx(share)
    # padding is work done, not work needed: the rung's own rows read higher
    assert ck.prefill_flops(graph, 12288) > ck.prefill_flops(graph, 12288 * real)
    # programs that do a rung's every row at the MXU's own speed read under 100 %
    fast = [{"label": "prefill:b12288[kernel]",
             "device_s": ck.prefill_flops(graph, 12288, 0.26) / 197e12}]
    assert reader("kernel.mla_prefill_mxu_roofline")(fake_run(config, AFTER, BEFORE, fast)) < 100


def test_a_program_without_the_counters_gives_the_readers_nothing(config):
    """The parent of ISSUE 43 cannot run the cell; a program with no
    ``mla.*`` counters (another family's snapshot) gives None and raises
    nothing."""
    programs = [{"label": "decode_k:k16:w16384[kernel]", "device_s": 0.136}]
    moe_only = {k: v for k, v in AFTER.items() if k.startswith("moe.")}
    for run in (fake_run(config, None, None, programs),
                fake_run(config, moe_only, None, programs),
                fake_run(config, BEFORE, BEFORE, programs),
                fake_run(config, AFTER, BEFORE, None)):
        assert reader("kernel.mla_decode_hbm_roofline")(run) is None
    for run in (fake_run(config, None), fake_run(config, moe_only), fake_run(config, BEFORE, BEFORE)):
        assert reader("mla.cache_read_share")(run) is None
        assert reader("mla.needed_read_share")(run) is None
    prompts = [{"label": "prefill:b12288[kernel]", "device_s": 0.5}]
    for run in (fake_run(config, None, None, prompts),
                fake_run(config, moe_only, None, prompts),
                fake_run(config, BEFORE, BEFORE, prompts),
                fake_run(config, AFTER, BEFORE, programs),
                fake_run(config, AFTER, BEFORE, None)):
        assert reader("kernel.mla_prefill_mxu_roofline")(run) is None


# -------------------------------------------------------------------- judge


def test_the_judge_holds_the_tokens_and_the_parts_to_their_own_limits(config):
    judge = frame.named_module("judges", "token_logits_and_parts")
    limits = config["reference"]
    assert limits["parts_probe_tokens"] == 8192  # past YaRN's 4,096, 32 blocks of the table
    sound = {"logit_deficit_max": 0.35, "argmax_agree_share": 0.92,
             "projection_rel_err_max": 0.0037, "attention_rel_err_max": 0.0069,
             "decode_read_rel_err_max": 0.0055}
    assert judge.judge(sound, limits)
    assert len(judge.compared(sound, limits)) == 5
    for name in ("projection", "attention", "decode_read"):
        wrong = {**sound, name + "_rel_err_max": 2 * limits[name + "_rel_err_limit"]}
        assert not judge.judge(wrong, limits), name
    assert not judge.judge({**sound, "logit_deficit_max": 2 * limits["logit_margin"]}, limits)
    assert not judge.judge({**sound, "argmax_agree_share": 0.3}, limits)
    # a configuration without the parts' probe is judged on its tokens alone
    tokens_only = {k: v for k, v in limits.items() if k != "parts_probe_tokens"}
    assert len(judge.compared(sound, tokens_only)) == 2
