"""ISSUE 28's cell, as the harness finds it: the configuration, its
reference kind and the traffic mix by name; the configuration file against
the published config it cites; the costs and the readers on numbers worked
out by hand; a rehearsal of the cell on the CPU that ends ``correct``, and
one with the served tokens altered that does not."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import costs_cohere2_moe as cm
import frame
import peaks
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "command-a-plus-l4-ep8.long-decode-closed"
CONFIG = "command-a-plus-l4-ep8"


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


# ------------------------------------------------------------ found by name


def test_the_cell_its_configuration_kind_judge_and_mix_are_found_by_name(manifest, config):
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    mix = load(f"benchmark/traffic/{cell['traffic']}.json")
    assert (mix["loop"], mix["route"], mix["clients"], mix["pool"]) == ("closed", "stream", 48, 256)
    assert mix["prompt_len"] == {"dist": "uniform", "min": 3072, "max": 5056}
    assert mix["output_len"] == {"dist": "uniform", "min": 512, "max": 1024}
    assert (mix["lead_in_s"], mix["drain_s"], mix["trace_slice_s"]) == (8.0, 40.0, 3.0)
    kind = frame.named_module("kinds", config["reference"]["kind"])
    assert callable(kind.check) and kind.JUDGE == "token_logits"
    assert callable(frame.named_module("judges", kind.JUDGE).judge)
    # every metric the cell reports has a reader file under its own name
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if "workloads" not in m or CELL in m["workloads"]:
                assert callable(frame.named_module(os.path.join(BENCH, "metrics"), m["name"]).read)
    mine = {m["name"] for g in ("end_to_end", "per_layer") for m in manifest[g]
            if CELL in m.get("workloads", [])}
    assert mine == {"tokens_per_s", "step.decode_ms", "kernel.moe_decode_hbm_roofline",
                    "step.prefill_share", "moe.tokens_per_held_expert", "moe.load_imbalance"}


def test_the_file_holds_every_published_number_but_the_reduced(manifest, config):
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    reduced = set(entry["reduced"])
    assert reduced == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"}
    for key, value in config["published"].items():
        if key in reduced:
            assert config[key] != value, key
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"],
            config["max_position_embeddings"]) == (4, 16, 32768, 8192)
    for key in ("assumed", "deployment", "note", "reference"):
        assert config[key]
    assert "8 chips share each layer" in config["deployment"]


def test_the_graph_runs_the_published_widths(config, graph):
    pub = config["published"]
    assert graph["family"] == "cohere2_moe"
    assert (graph["hidden"], graph["n_heads"], graph["n_kv_heads"], graph["head_dim"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["num_key_value_heads"], pub["head_dim"])
    assert (graph["ffn"], graph["n_experts"], graph["experts_per_tok"], graph["n_shared_experts"]) == (
        pub["intermediate_size"], pub["num_experts"], pub["num_experts_per_tok"], pub["num_shared_experts"])
    assert (graph["sliding_window"], graph["rope_theta"], graph["norm_eps"]) == (
        pub["sliding_window"], pub["rope_theta"], pub["layer_norm_eps"])
    assert graph["experts_held"] == config["experts_held"] == "0:16"
    period = pub["layer_types"][: graph["layer_pattern"]]
    assert period == ["sliding_attention"] * 3 + ["full_attention"]
    assert graph["vocab_size"] == config["vocab_size"] and graph["max_seq"] == config["max_position_embeddings"]
    # the pool holds what the mix can ask of every slot
    mix = load("benchmark/traffic/long-decode-closed.json")
    need = -(-(mix["prompt_len"]["max"] + mix["output_len"]["max"]) // graph["kv_block_size"])
    assert graph["kv_blocks"] >= 1 + graph["n_slots"] * need


# -------------------------------------------------------------------- costs


def test_the_share_is_the_issues_count(graph):
    assert cm.attention_params(graph) == 2 * 4096 * 16384 + 2 * 4096 * 1024 == 142_606_336
    assert cm.expert_params(graph) == 3 * 4096 * 4096 == 50_331_648
    assert cm.held(graph) == 16 and cm.full_layers(graph) == 1
    assert cm.layer_params(graph) == 142_606_336 + 20 * 50_331_648 + 4096 * 128 + 4096
    assert round(cm.share_params(graph) / 1e9, 2) == 4.73
    assert round(2 * cm.share_params(graph) / 1e9, 2) == 9.47
    assert cm.kv_bytes_per_token_layer(graph) * graph["n_layers"] == 16 * 1024


def test_a_decode_steps_bytes_follow_the_experts_touched_and_the_window(graph):
    dense = 2 * (4 * (cm.dense_layer_params(graph)) + 32768 * 4096 + 4096)
    assert cm.decode_step_bytes(graph, [], 0.0) == dense
    assert cm.decode_step_bytes(graph, [], 64.0) - dense == 64 * 2 * 50_331_648
    # inside the window every layer reads the context; past it the three
    # sliding layers stop at 4,096 and the full layer goes on
    assert cm.kv_tokens_read(graph, [1000]) == 4 * 1000
    assert cm.kv_tokens_read(graph, [6000]) == 6000 + 3 * 4096
    assert cm.kv_tokens_read(graph, [4096, 4097]) == 4 * 4096 + 4097 + 3 * 4096
    full = cm.decode_step_bytes(graph, [4450.0] * 32, 64.0)
    assert 11.6e9 < full < 11.7e9  # 9.47 GB of weights + 2.19 GB of K/V (the issue: "about 2 GB")
    assert full / 819e9 > 0.014


def test_a_prefills_flops_are_the_issues_fifteen_teraflop(graph):
    assert 14.5e12 < cm.prefill_flops(graph, 4064) < 15.5e12
    # the scores: causal under the window, the window's band past it
    a, b = cm.prefill_flops(graph, 4096), cm.prefill_flops(graph, 4097)
    per_token = 2 * 4 * (cm.attention_params(graph) + 4 * cm.expert_params(graph)
                         + 4096 * 128 + 1.0 * cm.expert_params(graph))
    assert b - a == pytest.approx(per_token + 4 * 128 * 128 * (4097 + 3 * 4096), rel=1e-9)


# ------------------------------------------------------------------ readers


def fake_run(config, counters_after, counters_before=None, programs=None, busy_s=3.0):
    def snap(c):
        return {"breakdown": {"generation": {"cohere2_moe:default": {"counters": c}}}}

    return types.SimpleNamespace(
        config=config, mix=load("benchmark/traffic/long-decode-closed.json"),
        before=snap(counters_before) if counters_before is not None else {"breakdown": {}},
        after=snap(counters_after) if counters_after is not None else {"breakdown": {}},
        trace=None if programs is None else {"programs": programs, "busy_s": busy_s},
        peaks=peaks.peaks_of("TPU v5 lite"), chips=1, traffic=traffic,
    )


def reader(name):
    return frame.named_module(os.path.join(BENCH, "metrics"), name).read


def test_the_moe_readers_read_the_counters_delta(config):
    before = {"moe.pairs_routed": 100, "moe.pairs_held": 10, "moe.experts_touched": 9,
              "moe.max_tokens_on_expert": 5, "moe.steps": 2}
    steps = 1000
    after = {"moe.pairs_routed": 100 + steps * 32 * 8 * 4, "moe.pairs_held": 10 + steps * 4 * 32,
             "moe.experts_touched": 9 + steps * 4 * 14, "moe.max_tokens_on_expert": 5 + steps * 4 * 6,
             "moe.steps": 2 + steps}
    run = fake_run(config, after, before)
    # 32 tokens x 8 picks x 16 / 128 held = 32 pairs a layer over 16 experts
    assert reader("moe.tokens_per_held_expert")(run) == pytest.approx(2.0)
    assert reader("moe.load_imbalance")(run) == pytest.approx(3.0)


def test_a_program_without_counters_gives_the_readers_nothing(config):
    """The parent of ISSUE 28 has no ``counters`` in its snapshot: the new
    readers return None there and raise nothing."""
    programs = [{"label": "decode_k:k16:w8192[kernel]", "device_s": 0.32}]
    for run in (fake_run(config, None, None, programs),
                fake_run(config, {"moe.steps": 5}, {"moe.steps": 5}, programs)):
        for name in ("moe.tokens_per_held_expert", "moe.load_imbalance",
                     "kernel.moe_decode_hbm_roofline"):
            assert reader(name)(run) is None


def test_the_roofline_share_counts_touched_experts_and_windowed_kv(config, graph):
    steps = 160
    after = {"moe.pairs_held": steps * 128, "moe.experts_touched": steps * 4 * 14,
             "moe.max_tokens_on_expert": steps * 24, "moe.steps": steps, "moe.pairs_routed": 0}
    programs = [{"label": "decode_k:k16:w8192[kernel]", "device_s": 16 * 0.020}] * 10 + [
        {"label": "prefill:b8192[kernel]", "device_s": 0.30}, {"label": "suffix:b256:w4096", "device_s": 0.05}]
    run = fake_run(config, after, {}, programs)
    share = reader("kernel.moe_decode_hbm_roofline")(run)
    # 56 experts touched a step: 3.02 GB dense + head, 5.64 GB experts,
    # 2.15 GB of K/V inside the windows: 10.8 GB at 819 GB/s against 20 ms
    assert 65.0 < share < 67.0
    slower = fake_run(config, after, {}, [dict(p, device_s=p["device_s"] * 2) for p in programs])
    assert reader("kernel.moe_decode_hbm_roofline")(slower) == pytest.approx(share / 2)
    all_held = dict(after, **{"moe.experts_touched": steps * 64})
    more = reader("kernel.moe_decode_hbm_roofline")(fake_run(config, all_held, {}, programs))
    assert more - share == pytest.approx(100 * 8 * 2 * 50_331_648 / 819e9 / 0.020, rel=1e-6)
    assert reader("step.prefill_share")(run) == pytest.approx(100 * 0.35 / 3.0)
    assert reader("step.decode_ms")(run) == pytest.approx(20.0)
    assert reader("step.prefill_share")(fake_run(config, after, {})) is None


# ------------------------------------------------- the kind, and a rehearsal


def test_the_kind_holds_probe_tokens_to_the_plain_forward_pass():
    """The reference child on the rehearsal's sizes: tokens no engine chose
    lie far under the reference's best; the positions are all counted."""
    import run

    rehearsal = os.path.join(ROOT, "benchmark", "rehearsal", f"{CONFIG}.json")
    probes = {"probes": [
        {"prompt": p, "tokens": [(13 * i + 5 * k) % 255 + 1 for i in range(32)]}
        for k, p in enumerate(run.probe_prompts(256))
    ]}
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference", "check.py"),
         "--config", rehearsal, "--seed", "7"],
        input=json.dumps(probes) + "\n", cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode == 0, done.stderr[-3000:]
    found = json.loads(done.stdout.strip().splitlines()[-1])
    assert found["kind"] == "cohere2_moe_decoder" and found["judge"] == "token_logits"
    assert found["positions"] == 128 and found["argmax_agree_share"] < 0.2
    assert found["logit_deficit_max"] > 1.0
    assert {"weights", "forward"} <= set(found["child_seconds"])


BROKEN = '''import sys

sys.path.insert(0, "benchmark")
import loadgen
import run

broke = "--break" in sys.argv
sys.argv = [a for a in sys.argv if a != "--break"]
whole = loadgen.stream_request


async def one_token_altered(session, base, body, s, vocab, keep_tokens=None):
    await whole(session, base, body, s, vocab, keep_tokens)
    if keep_tokens and broke:
        keep_tokens[5] = (keep_tokens[5] + vocab // 2) % (vocab - 1) + 1


loadgen.stream_request = one_token_altered
sys.exit(run.main())
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """The committed benchmark in a directory of its own, with one file
    beside it that alters a probe's served token where the client takes it."""
    root = str(tmp_path_factory.mktemp("cell"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(ROOT, "seldon_core_tpu"), os.path.join(root, "seldon_core_tpu"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(root, "broken.py"), "w") as f:
        f.write(BROKEN)
    return root


@pytest.mark.parametrize("broken", [False, True])
def test_a_rehearsal_of_the_cell_is_correct_and_an_altered_token_is_not(checkout, broken):
    """``run.py --rehearse-cpu`` on the cell's own traffic (3,072 to 5,056
    tokens in, 512 to 1,024 out, 48 clients) at the rehearsal's sizes
    reaches its result line: the family boots through ``JAX_GENERATIVE``,
    warms every program, serves the streams, its counters reach
    ``/stats/summary`` and the readers; the kind and the judge are found."""
    done = subprocess.run(
        [sys.executable, "broken.py", "--rehearse-cpu", "--workload", CELL,
         "--seed", "2147483659", "--seconds", "4"] + ["--break"] * broken,
        cwd=checkout, capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    infos = [json.loads(line[2:]) for line in lines[:-1] if line.startswith("# ")]
    checked = next(i for i in infos if "checks" in i)
    assert checked["reference"]["kind"] == "cohere2_moe_decoder"
    assert checked["xla_compiles_since_ready"] == 0 and checked["checks"]["no_failure_outside_window"]
    assert result["correct"] is not broken and result["rehearsal"] is True and result["failed"] == 0
    last = done.stderr.strip().splitlines()
    deficit = float(next(l for l in last if l.startswith("compared logit_deficit_max")).split()[2])
    assert (deficit > 0.5) is broken
    if not broken:
        ran = next(i for i in infos if i.get("rehearsal"))["readers_ran"]
        assert ran["end_to_end"] == ["setup_s", "tokens_per_s"]
        assert {"moe.load_imbalance", "moe.tokens_per_held_expert"} <= set(ran["per_layer"])
