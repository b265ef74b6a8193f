"""ISSUE 45's cell, as the harness finds it: the configuration, its reference
kind, its judge and the traffic mix by name; the configuration file against
the published config it cites; the costs against the issue's table; the
readers on a made-up pair of ``/stats/summary`` snapshots and a made-up
trace; the kind and the judge at the rehearsal's size.  Presence, not exact
lists or last place (PERF.md §7 (9))."""

import json
import os
import types

import pytest

import costs_jamba as cj
import frame
import peaks
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "ai21-jamba2-3b.many-slots-closed"
CONFIG = "ai21-jamba2-3b"
REDUCED = {"max_position_embeddings"}
NEW = ("kernel.ssm_decode_hbm_roofline", "ssm.state_share", "ssm.live_slots")
# its reader and costs are here and tested; it is in no cell's list: the
# trace's reduction keeps the ten longest operations, and the recurrence's
# kernel is not among them (PERF.md §7 "Open in the benchmark")
UNLISTED = "kernel.ssm_scan_hbm_roofline"


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
    assert (mix["loop"], mix["route"], mix["clients"], mix["pool"]) == ("closed", "stream", 192, 2048)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert mix["output_len"] == {"dist": "uniform", "min": 384, "max": 1152}
    assert (mix["lead_in_s"], mix["drain_s"], mix["trace_slice_s"]) == (30.0, 40.0, 3.0)
    assert mix["temperature"] == 0.0
    kind = frame.named_module("kinds", config["reference"]["kind"])
    assert callable(kind.check) and kind.JUDGE == "token_logits_and_state"
    assert callable(frame.named_module("judges", kind.JUDGE).judge)
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]
            if CELL in m.get("workloads", [])}
    assert mine >= {"tokens_per_s", "step.decode_ms", "step.prefill_share",
                    "sched.chained_share", "sched.sync_ms_per_block", *NEW}
    # that metric's cells are listed exactly by its own test (PERF.md §7 (9))
    assert "step.prefill_padding_share" not in mine
    for name in mine:
        assert callable(reader(name))
    new = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert new[name]["moves"] == "tokens_per_s" and new[name]["better"] == "higher"
    assert new["kernel.ssm_decode_hbm_roofline"]["source"] == "device_trace"
    assert new["kernel.ssm_decode_hbm_roofline"]["layer"] == "kernels"
    assert UNLISTED not in new and callable(reader(UNLISTED))
    assert new["ssm.state_share"]["source"] == new["ssm.live_slots"]["source"] == "program_counter"
    assert (new["ssm.state_share"]["unit"], new["ssm.live_slots"]["unit"]) == ("%", "slots")
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert len(manifest["workloads"]) >= 8


def test_the_file_holds_every_published_number_but_the_reduced(manifest, config):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "AI21-Jamba2-3B")
        assert config["published"] == row["config"] and config["source"] == row["source_url"]
    for key, value in config["published"].items():
        if key in REDUCED:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert set(config["reduced"]) == REDUCED and config["max_position_embeddings"] == 4096
    assert config["ssm_state_dtype"] == "float32"
    for key in ("assumed", "deployment", "note", "reference", "model"):
        assert config[key]
    assert {"layer_pattern", "head_dim", "ssm_init", "precision", "idle_keys"} <= set(config["assumed"])
    for said in ("one chip is the deployment", "3.029 B", "6.06 GB", "9.32 MB", "7.55 GB"):
        assert said in config["deployment"], said


def test_the_graph_runs_the_published_model_whole(config, graph):
    pub = config["published"]
    assert graph["family"] == "jamba"
    assert (graph["hidden"], graph["n_layers"], graph["n_heads"], graph["n_kv_heads"]) == (
        pub["hidden_size"], pub["num_hidden_layers"], pub["num_attention_heads"],
        pub["num_key_value_heads"])
    assert (graph["ffn"], graph["vocab_size"], graph["norm_eps"]) == (
        pub["intermediate_size"], pub["vocab_size"], pub["rms_norm_eps"])
    for key in ("attn_layer_period", "attn_layer_offset", "mamba_d_state", "mamba_d_conv",
                "mamba_expand", "mamba_dt_rank", "mamba_conv_bias", "mamba_proj_bias"):
        assert graph[key] == pub[key], key
    assert graph["max_seq"] == config["max_position_embeddings"]
    assert graph["ssm_state_dtype"] == "float32" and graph["dtype"] == "bfloat16"
    assert "decode_kernel" not in graph  # the program chooses
    for control in ("ssm_product_dtype", "ssm_padding", "conv_tail_at", "dt_bias"):
        assert control not in graph  # never served
    # the pool holds what the mix can ask of every slot
    mix = load("benchmark/traffic/many-slots-closed.json")
    need = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"]) // graph["kv_block_size"])
    assert graph["kv_blocks"] == 1 + graph["n_slots"] * need == 1153
    # the rehearsal walks the same slots, blocks and pool at tiny widths
    small = load(f"benchmark/rehearsal/{CONFIG}.json")["graph"]["parameters"]
    for key in ("n_slots", "decode_block", "kv_block_size", "kv_blocks", "max_seq"):
        assert small[key] == graph[key], key
    assert small["family"] == "jamba" and small["seq_impl"] == "dense"


def test_the_programs_config_takes_the_graph(graph):
    from seldon_core_tpu.models import jamba

    kind = frame.named_module("kinds", "jamba_decoder")
    cfg = kind.stated(graph)
    assert cfg.attn_layers == (7, 21) and cfg.d_inner == 5120 and cfg.max_seq == 4096
    fields = {f.name for f in __import__("dataclasses").fields(jamba.Config)}
    extra = {"family", "dtype", "seq_impl", "n_slots", "decode_block", "kv_block_size", "kv_blocks"}
    assert set(graph) - extra <= fields


# -------------------------------------------------------------------- costs


def test_the_parameters_and_bytes_are_the_issues_table(graph):
    near = lambda got, want: abs(got - want) <= 1e-3 * want  # noqa: E731  (0.1 %)
    assert near(cj.ssm_mixer_params(graph), 41.24e6)
    assert near(cj.attention_params(graph), 13.76e6)
    assert cj.mlp_params(graph) == 3 * 2560 * 8192 and near(cj.mlp_params(graph), 62.91e6)
    assert near(cj.ssm_layer_params(graph), 104.16e6)
    assert near(cj.attn_layer_params(graph), 76.68e6)
    assert near(cj.embedding_params(graph), 167.77e6)
    assert near(cj.total_params(graph), 3.029e9)
    assert near(cj.decode_weight_bytes(graph), 6.06e9)
    assert (cj.ssm_layers(graph), cj.attn_layers(graph)) == (26, 2)
    assert near(cj.slot_state_bytes(graph), 9.32e6)
    assert cj.slot_state_bytes(graph) == 26 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert round(128 * cj.slot_state_bytes(graph) / 1e9, 2) == 1.19
    assert cj.kv_row_bytes(graph) == 512 and 2 * cj.kv_row_bytes(graph) == 1024
    assert near(1153 * 256 * 1024, 0.302e9)
    args = cj.decode_weight_bytes(graph) + 128 * cj.slot_state_bytes(graph) + 1153 * 256 * 1024
    assert near(args, 7.55e9)
    # what the program itself makes is what the table says
    from seldon_core_tpu.models import jamba

    kind = frame.named_module("kinds", "jamba_decoder")
    cfg = kind.stated(graph)
    assert jamba.slot_state_bytes(cfg, "bfloat16") == cj.slot_state_bytes(graph)
    assert jamba.paged_kv_slot_bytes(cfg, 256, dtype="bfloat16") == (
        4096 * 1024 + cj.slot_state_bytes(graph))


def test_a_decode_steps_bytes_follow_the_counters(graph):
    fixed = cj.decode_weight_bytes(graph)
    assert cj.decode_step_bytes(graph, 0.0, 0.0) == fixed
    assert cj.decode_step_bytes(graph, 100.0, 0.0) - fixed == 100 * 2 * cj.slot_state_bytes(graph)
    assert cj.decode_step_bytes(graph, 0.0, 1000.0) - fixed == 512_000
    # the issue's step: 128 slots at a mean context of 1,050 on two layers
    step = cj.decode_step_bytes(graph, 128.0, 2 * 128 * 1050.0)
    assert 8.5e9 < step < 8.7e9 and 10.3e-3 < step / 819e9 < 10.7e-3
    assert 0.27 < cj.decode_state_bytes(graph, 128.0) / step < 0.29
    # the recurrence: about 41 KB a token a layer; a prompt of 640 is 3.7 TFLOP
    assert cj.scan_token_bytes(graph) == 5120 * 8 + 128 == 41_088
    assert cj.scan_bytes(graph, 1000.0) == 26 * (41_088 * 1000 + cj.scan_prompt_bytes(graph))
    assert round(cj.prefill_flops(graph, 640) / 1e12, 1) == 3.7


# ------------------------------------------------------------------ readers

# a made-up pair of /stats/summary snapshots: a window of 3,000 decode steps
# at 125 live slots and a mean context of 1,050, and 380 prompts of 243,200
# real tokens in 311,296 rows of their rungs
BEFORE = {"ssm.prefill_tokens": 640, "ssm.prefill_rows": 1024, "ssm.steps": 16,
          "ssm.slot_steps": 16, "attn.rows_live": 2 * 16 * 700}
STEPS = 3000
AFTER = {
    "ssm.prefill_tokens": 640 + 243_200, "ssm.prefill_rows": 1024 + 311_296,
    "ssm.steps": 16 + STEPS, "ssm.slot_steps": 16 + STEPS * 125,
    "attn.rows_live": 2 * 16 * 700 + STEPS * 2 * 125 * 1050,
}


def fake_run(config, after, before=None, programs=None, ops=None):
    def snap(c):
        return {"breakdown": {"generation": {"jamba:default": {"counters": c}}}}

    trace = None
    if programs is not None:
        trace = {"programs": programs, "busy_s": 3.0,
                 "breakdown": {"device_ops": ops or []}}
    return types.SimpleNamespace(
        config=config, mix=load("benchmark/traffic/many-slots-closed.json"),
        before=snap(before) if before is not None else {"breakdown": {}},
        after=snap(after) if after is not None else {"breakdown": {}},
        trace=trace, peaks=peaks.peaks_of("TPU v5 lite"), chips=1, traffic=traffic,
    )


def test_the_counter_readers_give_the_numbers_by_hand(config, graph):
    run = fake_run(config, AFTER, BEFORE)
    assert reader("ssm.live_slots")(run) == pytest.approx(125.0)
    state = 2 * cj.slot_state_bytes(graph) * 125
    need = cj.decode_weight_bytes(graph) + state + 512 * 2 * 125 * 1050
    assert reader("ssm.state_share")(run) == pytest.approx(100 * state / need)
    assert 26 < reader("ssm.state_share")(run) < 29
    # slots standing empty: the share falls with them
    empty = {**AFTER, "ssm.slot_steps": 16 + STEPS * 40}
    assert reader("ssm.live_slots")(fake_run(config, empty, BEFORE)) == pytest.approx(40.0)
    assert reader("ssm.state_share")(fake_run(config, empty, BEFORE)) < 12


def test_the_decode_roofline_counts_each_live_slots_state_once(config, graph):
    programs = [{"label": "decode_k:k16:w4096[kernel]", "device_s": 16 * 0.0125}] * 9 + [
        {"label": "prefill:b1024[kernel]", "device_s": 0.06}]
    run = fake_run(config, AFTER, BEFORE, programs)
    share = reader("kernel.ssm_decode_hbm_roofline")(run)
    need = cj.decode_step_bytes(graph, 125.0, 2 * 125 * 1050.0)
    assert share == pytest.approx(100 * need / 819e9 / 0.0125)
    assert 75 < share < 90
    # a block cut by the slice's edge does not move the share (the median)
    cut = programs + [{"label": "decode_k:k16:w4096[kernel]", "device_s": 0.03}]
    assert reader("kernel.ssm_decode_hbm_roofline")(
        fake_run(config, AFTER, BEFORE, cut)) == pytest.approx(share)
    # a program that reads the state twice, or all 128 slots' where 125 were
    # live, at the roofline's own speed reads under 100 %
    twice = need + cj.slot_state_bytes(graph) * 125
    fast = [{"label": "decode_k:k16:w4096[kernel]", "device_s": 16 * twice / 819e9}]
    assert reader("kernel.ssm_decode_hbm_roofline")(fake_run(config, AFTER, BEFORE, fast)) < 100
    every = cj.decode_step_bytes(graph, 128.0, 2 * 125 * 1050.0)
    fast = [{"label": "decode_k:k16:w4096[kernel]", "device_s": 16 * every / 819e9}]
    assert reader("kernel.ssm_decode_hbm_roofline")(fake_run(config, AFTER, BEFORE, fast)) < 100


def test_the_scan_roofline_counts_real_tokens_over_the_kernels_own_time(config, graph):
    programs = [
        {"label": "prefill:b1024[kernel]", "device_s": 0.060},
        {"label": "prefill:b1024[kernel]", "device_s": 0.061},
        {"label": "prefill:b512[kernel]", "device_s": 0.031},
        {"label": "prefill:b1024[kernel]", "device_s": 0.020},  # cut by the slice's edge
        {"label": "decode_k:k16:w4096[kernel]", "device_s": 16 * 0.0125},
    ]
    ops = [["fusion.11", 0.5], ["ssm.scan.3", 0.030], ["flash_attention.2", 0.01]]
    run = fake_run(config, AFTER, BEFORE, programs, ops)
    share = reader("kernel.ssm_scan_hbm_roofline")(run)
    real = 243_200 / 311_296
    # a rung's usual time is the median of its programs: 0.060 here
    parts = [(1024, 1.0), (1024, 1.0), (512, 1.0), (1024, 0.020 / 0.060)]
    need = cj.scan_bytes(
        graph, sum(r * real * p for r, p in parts), sum(p for _, p in parts))
    assert share == pytest.approx(100 * need / 819e9 / 0.030)
    assert 5 < share < 60
    # a kernel that moved a rung's every row at HBM's own speed reads under 100 %
    rows = cj.scan_bytes(graph, sum(r * p for r, p in parts), sum(p for _, p in parts))
    fast = [["ssm.scan.3", rows / 819e9]]
    assert reader("kernel.ssm_scan_hbm_roofline")(
        fake_run(config, AFTER, BEFORE, programs, fast)) < 100
    # the kernel's calls at several call sites are one kernel's time
    split = [["ssm.scan.3", 0.020], ["ssm.scan.7", 0.010]]
    assert reader("kernel.ssm_scan_hbm_roofline")(
        fake_run(config, AFTER, BEFORE, programs, split)) == pytest.approx(share)


def test_a_program_without_the_counters_gives_the_readers_nothing(config):
    """The parent of ISSUE 45 cannot run the cell; a program with no
    ``ssm.*`` counters (another family's snapshot), no trace, or no such
    kernel among its operations gives None and raises nothing."""
    programs = [{"label": "decode_k:k16:w4096[kernel]", "device_s": 0.2},
                {"label": "prefill:b1024[kernel]", "device_s": 0.06}]
    ops = [["ssm.scan.3", 0.03]]
    other = {"moe.steps": 100, "mla.rows_live": 5}
    for name in NEW + (UNLISTED,):
        for run in (fake_run(config, None, None, programs, ops),
                    fake_run(config, other, None, programs, ops),
                    fake_run(config, BEFORE, BEFORE, programs, ops)):
            assert reader(name)(run) is None, name
    for name in ("kernel.ssm_decode_hbm_roofline", "kernel.ssm_scan_hbm_roofline"):
        assert reader(name)(fake_run(config, AFTER, BEFORE, None)) is None
    # the recurrence not among the ten longest operations, or not a kernel
    assert reader("kernel.ssm_scan_hbm_roofline")(
        fake_run(config, AFTER, BEFORE, programs, [["fusion.1", 1.0]])) is None
    assert reader("kernel.ssm_scan_hbm_roofline")(
        fake_run(config, AFTER, BEFORE, programs[:1], ops)) is None


# ------------------------------------------------------------ kind and judge


def test_the_judge_holds_the_tokens_and_the_state_to_their_own_limits(config):
    judge = frame.named_module("judges", "token_logits_and_state")
    limits = config["reference"]
    assert (limits["state_probe_tokens"], limits["state_probe_steps"]) == (1000, 64)
    sound = {"logit_deficit_max": 0.3, "argmax_agree_share": 0.95}
    sound.update({p + "_max": limits[p + "_limit"] / 2 for p in judge.PARTS})
    assert judge.judge(sound, limits)
    assert len(judge.compared(sound, limits)) == 2 + len(judge.PARTS) == 6
    for name in judge.PARTS:
        wrong = {**sound, name + "_max": 2 * limits[name + "_limit"]}
        assert not judge.judge(wrong, limits), name
    assert not judge.judge({**sound, "logit_deficit_max": 2 * limits["logit_margin"]}, limits)
    assert not judge.judge({**sound, "argmax_agree_share": 0.3}, limits)
    tokens_only = {k: v for k, v in limits.items() if k != "state_probe_tokens"}
    assert len(judge.compared(sound, tokens_only)) == 2


def test_the_kind_holds_the_rehearsals_model_and_refuses_every_control():
    """``mechanism`` at the rehearsal's size on the CPU: the served graph
    holds, and each control is refused by the part it breaks."""
    import dataclasses

    small = load(f"benchmark/rehearsal/{CONFIG}.json")
    limits, graph = small["reference"], small["graph"]["parameters"]
    kind = frame.named_module("kinds", limits["kind"])
    judge = frame.named_module("judges", kind.JUDGE)
    cfg, params, kw = kind.model(graph, 11)
    assert kw == {"period": 2, "offset": 1, "eps": 1e-06}

    def parts(**control):
        found = kind.mechanism(
            dataclasses.replace(cfg, **control), graph, params, 11,
            limits["state_probe_tokens"], limits["state_probe_steps"],
        )
        return {p: found[p + "_max"] <= limits[p + "_limit"] for p in judge.PARTS}, found

    holds, found = parts()
    assert all(holds.values()), found
    assert found["state_probe_rung"] == 256 and found["state_layers_judged"] == 1
    for control, part in (
        (dict(ssm_state_dtype="bfloat16"), "state_rel_err"),
        (dict(ssm_product_dtype="bfloat16"), "state_rel_err"),
        (dict(ssm_padding="moves"), "state_rel_err"),
        (dict(conv_tail_at="rung"), "projection_rel_err"),
        (dict(dt_bias="off"), "projection_rel_err"),
    ):
        holds, found = parts(**control)
        assert not holds[part], (control, found)


def test_the_kind_judges_an_engines_probes_at_the_rehearsals_size():
    """``check`` on probes made by the program's own forward pass: what the
    reference child does with a run's probes, without the engine."""
    import jax.numpy as jnp
    import numpy as np

    from seldon_core_tpu.models import jamba

    small = load(f"benchmark/rehearsal/{CONFIG}.json")
    graph = small["graph"]["parameters"]
    kind = frame.named_module("kinds", small["reference"]["kind"])
    cfg, params, _ = kind.model(graph, 5)
    prompt = [int(t) for t in np.random.default_rng(2).integers(1, 256, 24)]
    toks, seq = [], list(prompt)
    for _ in range(6):
        lg = jamba.forward(params, jnp.asarray(seq)[None], cfg)[0, -1]
        toks.append(int(jnp.argmax(lg)))
        seq.append(toks[-1])
    found = kind.check(small, graph, 5, 1, {"probes": [{"prompt": prompt, "tokens": toks}]})
    found["judge"] = kind.JUDGE
    judge = frame.named_module("judges", kind.JUDGE)
    assert found["positions"] == 6 and found["argmax_agree_share"] == 1.0
    assert judge.judge(found, small["reference"]), judge.compared(found, small["reference"])
