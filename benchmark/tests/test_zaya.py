"""ISSUE 49's cell, as the harness finds it: the configuration, its reference
kind, its judge and the traffic mix by name; the configuration file against
the catalog row it cites; the costs against the issue's hand counts; the
three readers on a made-up pair of ``/stats/summary`` snapshots and a made-up
trace; the kind and the judge at the rehearsal's size.  Presence, not exact
lists or last place (PERF.md §7 (9))."""

import json
import os
import types

import pytest

import costs_zaya as cz
import frame
import peaks
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "zaya1-8b-l20.reasoning-closed"
CONFIG = "zaya1-8b-l20"
REDUCED = {"num_hidden_layers", "max_position_embeddings"}
NEW = ("kernel.zaya_decode_hbm_roofline", "cca.kv_read_share", "moe.skipped_share")
JOINED = ("step.decode_ms", "step.prefill_share", "sched.chained_share",
          "sched.sync_ms_per_block", "moe.tokens_per_held_expert",
          "moe.load_imbalance", "moe.read_touched_share")


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
    assert set(entry["reduced"]) == REDUCED
    mix = load(f"benchmark/traffic/{cell['traffic']}.json")
    assert (mix["loop"], mix["route"], mix["clients"]) == ("closed", "stream", 72)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 1024, "max": 2048}
    assert mix["output_len"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert (mix["lead_in_s"], mix["drain_s"], mix["trace_slice_s"]) == (30.0, 40.0, 3.0)
    assert mix["temperature"] == 0.0
    # a pool of prepared prompts three times what a run sends or more: the
    # 72 callers' first requests and 2.7 requests/s over lead-in and window
    assert mix["pool"] >= 3 * (72 + 2.7 * 70)
    kind = frame.named_module("kinds", config["reference"]["kind"])
    assert callable(kind.check) and kind.JUDGE == "token_logits_and_choice"
    assert callable(frame.named_module("judges", kind.JUDGE).judge)
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]
            if CELL in m.get("workloads", [])}
    assert mine >= {"tokens_per_s", *JOINED, *NEW}
    for name in mine:
        assert callable(reader(name))
    new = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert new[name]["moves"] == "tokens_per_s" and new[name]["unit"] == "%"
        assert new[name]["workloads"] == [CELL]
    assert new["kernel.zaya_decode_hbm_roofline"]["source"] == "device_trace"
    assert new["kernel.zaya_decode_hbm_roofline"]["layer"] == "kernels"
    assert new["cca.kv_read_share"]["source"] == new["moe.skipped_share"]["source"] == "program_counter"
    assert new["cca.kv_read_share"]["layer"] == new["moe.skipped_share"]["layer"] == "model step"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) >= 9


def test_the_file_holds_every_published_number_but_the_reduced(config):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
        assert config["published"] == row["config"] and config["source"] == row["source_url"]
    for key, value in config["published"].items():
        if key in REDUCED:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert set(config["reduced"]) == REDUCED
    assert (config["num_hidden_layers"], config["max_position_embeddings"]) == (20, 4096)
    for key in ("assumed", "deployment", "note", "reference", "model"):
        assert config[key]
    # (a) .. (i), each with its source
    assert len(config["assumed"]) == 9
    for letter, text in zip("abcdefghi", config["assumed"].values()):
        assert text.startswith(f"({letter}) "), text[:20]
    for said in ("two chips, each a pipeline stage of 20 blocks", "207.58 M", "17.7 GB",
                 "9.38 GB", "4.03 GB", "5.2 MB"):
        assert said in config["deployment"], said


def test_the_graph_runs_the_published_widths(config, graph):
    pub = config["published"]
    assert graph["family"] == "zaya"
    for key, name in (
        ("hidden", "hidden_size"), ("n_heads", "num_attention_heads"),
        ("n_kv_heads", "num_key_value_heads"), ("head_dim", "head_dim"),
        ("vocab_size", "vocab_size"), ("n_experts", "num_experts"),
        ("experts_per_tok", "num_experts_per_tok"), ("norm_eps", "rms_norm_eps"),
        ("moe_intermediate_size", "moe_intermediate_size"),
        ("router_hidden_size", "router_hidden_size"), ("cca_time0", "cca_time0"),
        ("cca_time1", "cca_time1"), ("partial_rotary_factor", "partial_rotary_factor"),
        ("tie_word_embeddings", "tie_word_embeddings"),
    ):
        assert graph[key] == pub[name], key
    assert graph["rope_theta"] == pub["rope_parameters"]["hybrid"]["rope_theta"]
    assert graph["n_layers"] == config["num_hidden_layers"] == pub["num_hidden_layers"] // 2
    assert graph["max_seq"] == config["max_position_embeddings"]
    assert graph["dtype"] == "bfloat16" and "decode_kernel" not in graph
    assert "control_weights" not in graph  # never served
    # the pool holds every slot at the served context
    assert graph["kv_blocks"] == 1 + graph["n_slots"] * graph["max_seq"] // graph["kv_block_size"]
    mix = load("benchmark/traffic/reasoning-closed.json")
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= graph["max_seq"]
    # the rehearsal walks the same slots, blocks and pool at tiny widths
    small = load(f"benchmark/rehearsal/{CONFIG}.json")["graph"]["parameters"]
    for key in ("n_slots", "decode_block", "kv_block_size", "kv_blocks", "max_seq"):
        assert small[key] == graph[key], key
    assert small["family"] == "zaya" and small["seq_impl"] == "dense"
    assert (small["n_layers"], small["hidden"], small["n_heads"], small["n_kv_heads"],
            small["n_experts"]) == (2, 32, 2, 1, 4)


def test_the_programs_config_takes_the_graph_and_the_published_file(config, graph):
    import dataclasses

    from seldon_core_tpu.models import zaya

    kind = frame.named_module("kinds", "zaya_decoder")
    cfg = kind.stated(graph)
    assert (cfg.latent, cfg.rotary_dim, cfg.max_seq, cfg.n_layers) == (1280, 64, 4096, 20)
    assert cfg == zaya.Config.from_published(config)  # the file as cut: 20 blocks, 4,096
    fields = {f.name for f in dataclasses.fields(zaya.Config)}
    extra = {"family", "dtype", "seq_impl", "n_slots", "decode_block", "kv_block_size", "kv_blocks"}
    assert set(graph) - extra == fields


# -------------------------------------------------------------------- costs


def test_the_parameters_and_bytes_are_the_issues_hand_counts(graph):
    assert cz.cca_params(graph) == 2_097_152 + 524_288 + 524_288 + 2_097_152 == 5_242_880
    assert cz.conv_params(graph) == 332_800 and cz.small_params(graph) == 20_482
    assert cz.router_params(graph) == 661_009
    assert cz.expert_params(graph) == 12_582_912 and 2 * cz.expert_params(graph) == 25_165_824
    assert cz.block_params(graph) == 207_583_763
    assert cz.embedding_params(graph) == 537_133_056
    assert round(cz.total_params(graph) / 1e9, 3) == 4.689
    assert round(2 * cz.total_params(graph) / 1e9, 2) == 9.38
    whole = {**graph, "n_layers": 40}
    assert round(cz.total_params(whole) / 1e9, 2) == 8.84
    assert round(2 * cz.total_params(whole) / 1e9, 1) == 17.7
    assert cz.kv_row_bytes(graph) == 1024 and 20 * cz.kv_row_bytes(graph) == 20_480
    assert cz.slot_tail_bytes(graph) == 20 * 5376 == 107_520
    pool = graph["kv_blocks"] * graph["kv_block_size"] * 20_480
    assert round(pool / 1e9, 2) == 4.03
    args = 2 * cz.total_params(graph) + pool + 48 * cz.slot_tail_bytes(graph)
    assert 13.40e9 < args < 13.43e9
    # what the program itself makes is what the table says
    from seldon_core_tpu.models import zaya

    cfg = frame.named_module("kinds", "zaya_decoder").stated(graph)
    assert zaya.slot_tail_bytes(cfg, "bfloat16") == cz.slot_tail_bytes(graph)
    assert zaya.paged_kv_slot_bytes(cfg, 256, dtype="bfloat16") == 4096 * 20_480 + 107_520


def test_a_decode_steps_bytes_follow_the_counters(graph):
    fixed = cz.decode_dense_bytes(graph)
    assert fixed == 2 * (20 * 6_257_171 + 537_133_056 + 2048)
    assert cz.decode_step_bytes(graph, 0.0, 0.0, 0.0) == fixed
    assert cz.decode_step_bytes(graph, 300.0, 0.0, 0.0) - fixed == 300 * 25_165_824
    assert cz.decode_step_bytes(graph, 0.0, 1000.0, 0.0) - fixed == 1_024_000
    assert cz.decode_step_bytes(graph, 0.0, 0.0, 1.0) - fixed == 20_480 + 2 * 107_520
    # the issue's step: 48 slots at a mean context of 2,050, 15 of 16 experts a block
    rows = 20 * 48 * 2050.0
    step = cz.decode_step_bytes(graph, 300.0, rows, 48.0)
    assert 10.8e9 < step < 11.0e9 and 13.2e-3 < step / 819e9 < 13.5e-3
    assert 0.68 < 300 * 25_165_824 / step < 0.70
    assert 0.18 < cz.decode_kv_bytes(graph, rows) / step < 0.19
    assert 0.095 < 2 * cz.embedding_params(graph) / step < 0.10
    assert round(cz.prefill_flops(graph, 2048) / 1e12, 2) == 1.71


# ------------------------------------------------------------------ readers

# a made-up pair of /stats/summary snapshots: a window of 4,000 decode steps at
# 46 live slots and a mean context of 2,050; 14.9 of 16 experts a block touched,
# one token-layer in 16 skipped; 108 prompts
BEFORE = {"zaya.steps": 16, "moe.steps": 16, "moe.pairs_routed": 16 * 20,
          "moe.pairs_held": 16 * 19, "moe.tokens_skipped": 16, "moe.experts_touched": 16 * 19,
          "moe.experts_read": 16 * 19, "moe.max_tokens_on_expert": 16 * 20,
          "attn.rows_live": 16 * 20 * 80}
STEPS = 4000
AFTER = {
    "zaya.steps": 16 + STEPS, "moe.steps": 16 + STEPS,
    "moe.pairs_routed": 16 * 20 + STEPS * 20 * 46,
    "moe.pairs_held": 16 * 19 + STEPS * 20 * 46 * 15 // 16,
    "moe.tokens_skipped": 16 + STEPS * 20 * 46 // 16,
    "moe.experts_touched": 16 * 19 + STEPS * 298,
    "moe.experts_read": 16 * 19 + STEPS * 298,
    "moe.max_tokens_on_expert": 16 * 20 + STEPS * 20 * 7,
    "attn.rows_live": 16 * 20 * 80 + STEPS * 20 * 46 * 2050,
}


def fake_run(config, after, before=None, programs=None):
    def snap(c):
        return {"breakdown": {"generation": {"zaya:default": {"counters": c}}}}

    trace = None
    if programs is not None:
        trace = {"programs": programs, "busy_s": 3.0, "breakdown": {"device_ops": []}}
    return types.SimpleNamespace(
        config=config, mix=load("benchmark/traffic/reasoning-closed.json"),
        before=snap(before) if before is not None else {"breakdown": {}},
        after=snap(after) if after is not None else {"breakdown": {}},
        trace=trace, peaks=peaks.peaks_of("TPU v5 lite"), chips=1, traffic=traffic,
    )


def test_the_counter_readers_give_the_numbers_by_hand(config, graph):
    run = fake_run(config, AFTER, BEFORE)
    assert reader("moe.skipped_share")(run) == pytest.approx(100 / 16)
    kv = 1024 * 20 * 46 * 2050
    need = cz.decode_step_bytes(graph, 298.0, 20 * 46 * 2050.0, 46.0)
    assert reader("cca.kv_read_share")(run) == pytest.approx(100 * kv / need)
    assert 17 < reader("cca.kv_read_share")(run) < 19
    # the readers the cell joins read K = 1 with skipped tokens as they say:
    # tokens a held expert sees count the held pairs alone
    assert reader("moe.tokens_per_held_expert")(run) == pytest.approx(46 * 15 / 16 / 16)
    assert reader("moe.load_imbalance")(run) == pytest.approx(7 / (46 * 15 / 16 / 16))
    assert reader("moe.read_touched_share")(run) == pytest.approx(100.0)
    # shorter contexts: the share falls with them
    short = {**AFTER, "attn.rows_live": 16 * 20 * 80 + STEPS * 20 * 46 * 500}
    assert reader("cca.kv_read_share")(fake_run(config, short, BEFORE)) < 6


def test_the_decode_roofline_is_the_whole_steps_share(config, graph):
    programs = [{"label": "decode_k:k16:w4096[kernel]", "device_s": 16 * 0.017}] * 9 + [
        {"label": "prefill:b2048[kernel]", "device_s": 0.02}]
    run = fake_run(config, AFTER, BEFORE, programs)
    share = reader("kernel.zaya_decode_hbm_roofline")(run)
    need = cz.decode_step_bytes(graph, 298.0, 20 * 46 * 2050.0, 46.0)
    assert share == pytest.approx(100 * need / 819e9 / 0.017)
    assert 70 < share < 85
    # a block cut by the slice's edge does not move the share (the median)
    cut = programs + [{"label": "decode_k:k16:w4096[kernel]", "device_s": 0.03}]
    assert reader("kernel.zaya_decode_hbm_roofline")(
        fake_run(config, AFTER, BEFORE, cut)) == pytest.approx(share)
    # a program that read every held expert where 298 of 320 were touched, at
    # the roofline's own speed, reads under 100 %
    every = cz.decode_step_bytes(graph, 320.0, 20 * 46 * 2050.0, 46.0)
    fast = [{"label": "decode_k:k16:w4096[kernel]", "device_s": 16 * every / 819e9}]
    assert reader("kernel.zaya_decode_hbm_roofline")(fake_run(config, AFTER, BEFORE, fast)) < 100


def test_a_program_without_the_counters_gives_the_readers_nothing(config):
    """The parent of ISSUE 49 cannot run the cell; a program with no such
    counters (another family's snapshot) or no trace gives None and raises
    nothing."""
    programs = [{"label": "decode_k:k16:w4096[kernel]", "device_s": 0.2}]
    other = {"moe.steps": 100, "ssm.steps": 100, "attn.rows_live": 5}
    for name in NEW:
        for run in (fake_run(config, None, None, programs),
                    fake_run(config, other, None, programs),
                    fake_run(config, BEFORE, BEFORE, programs)):
            assert reader(name)(run) is None, name
    assert reader("kernel.zaya_decode_hbm_roofline")(fake_run(config, AFTER, BEFORE, None)) is None


# ------------------------------------------------------------ kind and judge


def test_the_judge_holds_the_tokens_and_the_parts_to_their_own_limits(config):
    judge = frame.named_module("judges", "token_logits_and_choice")
    limits = config["reference"]
    assert (limits["state_probe_tokens"], limits["state_probe_steps"]) == (2000, 64)
    sound = {"logit_deficit_mean": limits["logit_deficit_mean_limit"] / 2,
             "argmax_agree_share": 0.95}
    sound.update({p + "_max": limits[p + "_limit"] / 2 for p in judge.PARTS})
    assert judge.judge(sound, limits)
    assert len(judge.compared(sound, limits)) == 2 + len(judge.PARTS) == 13
    for name in judge.PARTS:
        # the bookkeeping's limit is none at all
        wrong = {**sound, name + "_max": 2 * limits[name + "_limit"] or 1}
        assert not judge.judge(wrong, limits), name
    assert not judge.judge(
        {**sound, "logit_deficit_mean": 2 * limits["logit_deficit_mean_limit"]}, limits)
    assert not judge.judge({**sound, "argmax_agree_share": 0.0}, limits)
    # a differing choice is allowed only under the stated gap
    assert judge.judge({**sound, "choice_deficit_max": limits["choice_deficit_limit"]}, limits)
    assert not judge.judge(
        {**sound, "choice_deficit_max": 1.01 * limits["choice_deficit_limit"]}, limits)
    tokens_only = {k: v for k, v in limits.items() if k != "state_probe_tokens"}
    assert len(judge.compared(sound, tokens_only)) == 2


def test_the_kind_holds_the_rehearsals_model_and_refuses_the_int8_control():
    """``mechanism`` at the rehearsal's size on the CPU: the served graph
    holds every row, and the same weights rounded to int8 a column are
    refused — by the projections, the router's probabilities and the experts,
    not by the attention, which is judged on the program's own q, k, v."""
    small = load(f"benchmark/rehearsal/{CONFIG}.json")
    limits, graph = small["reference"], small["graph"]["parameters"]
    kind = frame.named_module("kinds", limits["kind"])
    judge = frame.named_module("judges", kind.JUDGE)
    cfg, params, kw = kind.model(graph, 11)
    assert kw == {"rotary_dim": 8, "theta": 5000000.0, "eps": 1e-05}

    def parts(graph):
        found = kind.mechanism(
            cfg, graph, params, 11, limits["state_probe_tokens"], limits["state_probe_steps"],
        )
        return {p: found[p + "_max"] <= limits[p + "_limit"] for p in judge.PARTS}, found

    holds, found = parts(graph)
    assert all(holds.values()), found
    assert found["state_probe_rung"] == 256 and found["blocks_judged"] == 2
    assert 0 < found["skipped_share"] < 0.5
    holds, found = parts({**graph, "control_weights": "int8"})
    for part in ("projection_rel_err", "router_prob_abs_err", "expert_rel_err"):
        assert not holds[part], (part, found)
    assert holds["attention_rel_err"] and holds["decode_read_rel_err"]
    # the entry functions ran the weights as served: no longer the composition's
    assert not holds["entry_first_block_rel_err"] and holds["entry_bookkeeping_faults"]
    assert found["entry_slots"] == 48
    with pytest.raises(ValueError):
        kind.mechanism(cfg, {**graph, "control_weights": "int4"}, params, 11, 200, 16)


LINK = ("entry_first_block_rel_err", "entry_early_blocks_median_rel_err",
        "entry_handoff_rel_err")
COMPOSED = ("projection_rel_err", "attention_rel_err", "decode_read_rel_err",
            "router_prob_abs_err", "expert_rel_err")


@pytest.mark.parametrize("plant, refused_by, held", [
    # the ENTRY functions on int8 weights beside the composition as served
    ({"control_entry": "int8"}, LINK, COMPOSED),
    # the slot's tails zeroed between the prompt's program and the first step's
    ({"control_entry": "tail_lost"},
     ("entry_first_block_rel_err", "entry_handoff_rel_err"), COMPOSED),
    # no block's router hears the one before: the third block's rows show it
    ({"control_entry": "z_dropped", "n_layers": 3}, ("entry_early_blocks_median_rel_err",),
     COMPOSED + ("entry_first_block_rel_err", "entry_handoff_rel_err")),
    # wrong reads planted in the composition: each by its own row
    ({"control_read": "block_off_by_one"}, ("decode_read_rel_err",), ("attention_rel_err",)),
    ({"control_read": "pos_minus_1"}, ("decode_read_rel_err",), ("attention_rel_err",)),
    ({"control_read": "prompt_v_rolled"}, ("attention_rel_err",), ("decode_read_rel_err",)),
], ids=lambda v: next(iter(v.values())) if isinstance(v, dict) else None)
def test_the_kind_refuses_each_planted_fault_by_its_own_rows(plant, refused_by, held):
    """The faults the entry link and the two reads' rows exist for, at the
    rehearsal's size: each is refused by the rows that should see it and not
    by the rows that should not."""
    small = load(f"benchmark/rehearsal/{CONFIG}.json")
    limits, graph = small["reference"], small["graph"]["parameters"]
    kind = frame.named_module("kinds", limits["kind"])
    cfg, params, _ = kind.model({**graph, **plant}, 11)
    found = kind.mechanism(
        cfg, {**graph, **plant}, params, 11, limits["state_probe_tokens"],
        limits["state_probe_steps"],
    )
    for part in refused_by:
        assert found[part + "_max"] > limits[part + "_limit"], (part, found[part + "_max"])
    for part in held + ("entry_bookkeeping_faults",):
        assert found[part + "_max"] <= limits[part + "_limit"], (part, found[part + "_max"])
    with pytest.raises(ValueError):
        kind.mechanism(cfg, {**graph, "control_read": "elsewhere"}, params, 11, 200, 16)


def test_the_kind_judges_an_engines_probes_at_the_rehearsals_size():
    """``check`` on probes made by the program's own forward pass: what the
    reference child does with a run's probes, without the engine."""
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.models import zaya

    small = load(f"benchmark/rehearsal/{CONFIG}.json")
    graph = small["graph"]["parameters"]
    kind = frame.named_module("kinds", small["reference"]["kind"])
    cfg, params, _ = kind.model(graph, 5)
    prompt = [int(t) for t in np.random.default_rng(2).integers(1, 256, 24)]
    toks, seq = [], list(prompt)
    for _ in range(6):
        lg = zaya.forward(params, jnp.asarray(seq)[None], cfg)[0, -1]
        toks.append(int(jnp.argmax(lg)))
        seq.append(toks[-1])
    limits = {k: v for k, v in small["reference"].items() if k != "state_probe_tokens"}
    found = kind.check({**small, "reference": limits}, graph, 5, 1,
                       {"probes": [{"prompt": prompt, "tokens": toks}]})
    found["judge"] = kind.JUDGE
    judge = frame.named_module("judges", kind.JUDGE)
    assert found["positions"] == 6 and found["argmax_agree_share"] == 1.0
    assert judge.judge(found, limits), judge.compared(found, limits)


# ------------------------------------------------------------ the step by scope


def test_scope_probe_gives_an_operation_the_innermost_scope_its_metadata_names():
    import scope_probe

    hlo = "\n".join([
        '  %fusion.7 = bf16[48,1280] fusion(%a), kind=kLoop, metadata={op_name="jit(step)/while/body/cca.conv/add"}',
        '  ROOT %fusion.9 = f32[48,17] fusion(%b), metadata={op_name="jit(step)/while/body/moe.experts/router.mlp/dot"}',
        '  %copy.3 = bf16[48,2048] copy(%c), metadata={op_name="jit(step)/while/body/transpose"}',
        '  %constant.1 = s32[] constant(0)',
    ])
    by = scope_probe.scope_of(hlo, scope_probe.SCOPES)
    assert by == {"fusion.7": "cca.conv", "fusion.9": "router.mlp", "copy.3": "other"}
    # every scope the family's programs name is one the probe looks for
    from seldon_core_tpu.models import zaya

    text = open(zaya.__file__).read()
    for scope in scope_probe.SCOPES:
        assert f'"{scope}"' in text or scope in ("attn.prompt", "attn.paged", "moe.experts", "head"), scope
