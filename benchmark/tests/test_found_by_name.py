"""What the harness takes as data: a reference kind, its judge and a traffic
mix with another arrival process are files of their own, found by name; the
two kinds there are find what they found before they moved behind that door;
the gamma gaps are the distribution's."""

import hashlib
import json
import math
import os
import shutil
import statistics
import subprocess
import sys

import pytest

import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
PROBE_NEW = 32


def check_child(root: str, config_path: str, seed: int, probes: dict, devices: int = 1) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "reference", "check.py"),
         "--config", config_path, "--seed", str(seed)],
        input=json.dumps(probes) + "\n", env=env, cwd=root,
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def stream_probes(vocab: int) -> dict:
    """The run's own probe prompts, answered with tokens that no engine
    chose: the reference's findings on them are numbers to pin."""
    import run

    return {"probes": [
        {"prompt": p, "tokens": [(13 * i + 5 * k) % (vocab - 1) + 1 for i in range(PROBE_NEW)]}
        for k, p in enumerate(run.probe_prompts(vocab))
    ]}


def row_probes(vocab: int) -> dict:
    import run

    rows = run.probe_rows(vocab, 128)
    return {"tokens": rows.tolist(),
            "outputs": [[0.25 + 0.5 * ((r + c) % 2) for c in range(2)] for r in range(len(rows))]}


# (b) what the parent's check.py (one file, a table of two kinds) gave for
# these probes at seed 7 on the CPU, read from a copy of the parent commit
FOUND_BEFORE_THE_MOVE = {
    "mistral-7b-l8": (1, {
        "kind": "llama_decoder", "positions": 128, "argmax_agree_share": 0.0,
        "logit_deficit_max": 5.96833610534668, "logit_deficit_p99": 5.486196517944336,
        "judge": "token_logits"}),
    "mistral-7b-tp4": (4, {  # four devices: two layers spread over two of them
        "kind": "llama_decoder", "positions": 128, "argmax_agree_share": 0.0078125,
        "logit_deficit_max": 5.994662761688232, "logit_deficit_p99": 5.794853210449219,
        "judge": "token_logits"}),
    "bert-base": (1, {
        "kind": "bert_encoder", "rows": 8, "prob_abs_err_max": 0.354891300201416,
        "judge": "class_probs"}),
}


@pytest.mark.parametrize("name", sorted(FOUND_BEFORE_THE_MOVE))
def test_the_kinds_there_are_find_what_they_found_before_the_move(name):
    devices, want = FOUND_BEFORE_THE_MOVE[name]
    path = os.path.join(BENCH, "rehearsal", name + ".json")
    with open(path) as f:
        config = json.load(f)
    stream = config["graph"]["implementation"] == "JAX_GENERATIVE"
    probes = (stream_probes if stream else row_probes)(config["vocab_size"])
    found = check_child(ROOT, path, 7, probes, devices)
    assert set(found) == set(want) | {"child_seconds"}
    for k, v in want.items():
        # float32 on one machine gives the last digit; another may round
        # a sum otherwise, and no further
        assert found[k] == (pytest.approx(v, abs=2e-5) if isinstance(v, float) else v), k


DECODER = {"kind": "k", "logit_margin": 0.25, "argmax_agree_min": 0.8}


@pytest.mark.parametrize("found,limits,verdict", [
    ({"logit_deficit_max": 0.142, "argmax_agree_share": 0.89}, DECODER, True),
    ({"logit_deficit_max": 0.25, "argmax_agree_share": 0.8}, DECODER, True),
    ({"logit_deficit_max": 0.26, "argmax_agree_share": 0.95}, DECODER, False),
    ({"logit_deficit_max": 0.05, "argmax_agree_share": 0.79}, DECODER, False),
    ({"logit_deficit_max": float("nan"), "argmax_agree_share": 0.9}, DECODER, False),
    # a configuration's own judge goes before its kind's
    ({"prob_abs_err_max": 0.003}, {"kind": "k", "judge": "class_probs", "prob_abs_tol": 0.03}, True),
    ({"prob_abs_err_max": 0.031}, {"kind": "k", "judge": "class_probs", "prob_abs_tol": 0.03}, False),
])
def test_the_two_judges_hold_a_finding_to_the_configurations_limits(found, limits, verdict):
    import run

    ok, rows = run.judge({**found, "judge": "token_logits"}, limits)
    assert ok is verdict and len(rows) == len(found)
    assert all(name in found and op in ("<=", ">=") for name, _, op, _ in rows)


def test_a_judge_that_is_not_there_fails_the_run_and_names_the_file():
    import run

    for name in ("no_such_judge", "../kinds/llama_decoder", ""):
        with pytest.raises(run.BenchFailure, match="judges"):
            run.judge({"judge": "token_logits"}, {"kind": "k", "judge": name or None} if name
                      else {"kind": "k", "judge": "has space"})


# ---------------------------------------------------------------- (a)

TOY_KIND = '''"""A kind of a later PR's own: it imports the frame, copies none of it."""
JUDGE = "toy_share"


def check(config, graph, seed, chips, probes):
    import frame

    assert frame.served_dtype(graph["dtype"]).__name__ == "bfloat16"
    toks = [t for p in probes["probes"] for t in p["tokens"]]
    frame.lap("toy")
    return {"kind": "toy_tokens", "positions": len(toks), "seed": seed, "chips": chips,
            "in_vocab_share": sum(0 <= t < config["vocab_size"] for t in toks) / len(toks)}
'''
TOY_JUDGE = '''def compared(found, limits):
    return [("in_vocab_share", found["in_vocab_share"], ">=", limits["in_vocab_min"])]


def judge(found, limits):
    return found["in_vocab_share"] >= limits["in_vocab_min"]
'''
# the rest of a run with the served path broken underneath: every probe's
# sixth token comes back as another one, before the window and after it
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
TOY_MIX = {
    "route": "stream", "loop": "open", "arrivals": {"process": "gamma", "cv": 2.0},
    "rate_per_s": 4.0, "lead_in_s": 1.0, "drain_s": 30.0,
    "prompt_len": {"dist": "uniform", "min": 16, "max": 48},
    "output_len": {"dist": "fixed", "value": 16}, "temperature": 0.0,
}


@pytest.fixture(scope="module")
def later_pr(tmp_path_factory):
    """A checkout as a later PR would leave it: every file of the benchmark
    as it is, and a kind, a judge, a mix, a configuration and their entries
    added.  ``add`` refuses a path that is there."""
    root = str(tmp_path_factory.mktemp("later_pr"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(ROOT, "seldon_core_tpu"), os.path.join(root, "seldon_core_tpu"))

    def add(rel: str, text: str) -> None:
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} is there already"
        with open(path, "w") as f:
            f.write(text)

    with open(os.path.join(BENCH, "rehearsal", "mistral-7b-l8.json")) as f:
        config = json.load(f)
    config["graph"]["parameters"]["max_seq"] = 256  # fewer programs to warm
    config["reference"] = {"kind": "toy_tokens", "in_vocab_min": 1.0, "why": "a test"}
    add("benchmark/reference/kinds/toy_tokens.py", TOY_KIND)
    add("benchmark/reference/judges/toy_share.py", TOY_JUDGE)
    add("benchmark/traffic/toy-gamma.json", json.dumps(TOY_MIX))
    add("benchmark/rehearsal/toy.json", json.dumps(config))
    # the same small engine under the decoder's kind and judge, for BROKEN
    with open(os.path.join(BENCH, "rehearsal", "mistral-7b-l8.json")) as f:
        decoder = json.load(f)
    decoder["graph"]["parameters"]["max_seq"] = 256
    add("benchmark/rehearsal/toy-decoder.json", json.dumps(decoder))
    add("broken.py", BROKEN)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name in ("toy", "toy-decoder"):
        cell = name + ".toy-gamma"
        manifest["configs"].append({"name": name, "file": f"benchmark/configs/{name}.json"})
        manifest["workloads"].append(
            {"name": cell, "config": name, "traffic": "toy-gamma", "chips": 1})
        for m in manifest["end_to_end"]:
            if m["name"] in ("ttft_ms_p95", "tpot_ms_p95"):
                m["workloads"].append(cell)
    add("BENCHMARK.json", json.dumps(manifest))
    return root, config


def test_check_py_finds_a_kind_that_a_later_pr_adds_as_a_file(later_pr):
    root, config = later_pr
    probes = stream_probes(config["vocab_size"])
    probes["probes"][0]["tokens"][3] = config["vocab_size"]  # one of 128 outside
    found = check_child(root, os.path.join(root, "benchmark", "rehearsal", "toy.json"), 11, probes)
    assert found["kind"] == "toy_tokens" and found["judge"] == "toy_share"
    assert found["positions"] == 128 and found["seed"] == 11 and found["chips"] == 1
    assert found["in_vocab_share"] == 127 / 128 and "toy" in found["child_seconds"]
    # ... and run.py its judge, which holds that finding to the file's limit
    code = ("import json, sys; sys.path.insert(0, 'benchmark'); import run; "
            "f, ref = json.loads(sys.stdin.readline()); print(json.dumps(run.judge(f, ref)))")
    for share, verdict in ((1.0, True), (127 / 128, False)):
        done = subprocess.run(
            [sys.executable, "-c", code], cwd=root, capture_output=True, text=True,
            input=json.dumps([{**found, "in_vocab_share": share}, config["reference"]]) + "\n",
        )
        assert done.returncode == 0, done.stderr[-2000:]
        assert json.loads(done.stdout) == [verdict, [["in_vocab_share", share, ">=", 1.0]]]
    # a configuration may name another judge than its kind's own
    with pytest.raises(subprocess.CalledProcessError):
        subprocess.run(
            [sys.executable, "-c", code], cwd=root, check=True, capture_output=True, text=True,
            input=json.dumps([found, {**config["reference"], "judge": "token_logits"}]) + "\n",
        )


def test_a_rehearsal_finds_the_kind_the_judge_and_the_gamma_mix(later_pr):
    root, _ = later_pr
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), "--rehearse-cpu",
         "--workload", "toy.toy-gamma", "--seed", "2147483659", "--seconds", "4"],
        cwd=root, capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
    )
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    infos = [json.loads(line[2:]) for line in lines[:-1] if line.startswith("# ")]
    checked = next(i for i in infos if "checks" in i)
    assert checked["reference"]["kind"] == "toy_tokens"
    assert checked["reference"]["judge"] == "toy_share"
    assert ["in_vocab_share", 1.0, ">=", 1.0] in checked["compared"]
    assert result["correct"] is True and result["rehearsal"] is True
    # 4 requests/s over 1 s of lead-in and 4 s of window: the gamma schedule's
    # requests due in the window (the 20th is due as it closes)
    dues = traffic.due_times(TOY_MIX, 5.0)
    assert len(dues) == 20 and dues[-1] == pytest.approx(5.0)
    assert result["attempted"] == sum(1.0 <= t < 4.999 for t in dues) and result["failed"] == 0
    assert "compared in_vocab_share: 1.0 >= 1.0" in done.stderr
    assert done.stderr.strip().splitlines()[-1].startswith("correct: True")


@pytest.mark.parametrize("broken", [False, True])
def test_a_run_whose_served_tokens_are_altered_is_not_correct(later_pr, broken):
    """The rest of a run without the look for a chip (a rehearsal), at the
    decoder's own kind and judge: sound, it is correct; with one token in 32
    of each probe altered where the client takes it, the reference finds
    the token far under its best and ``correct`` comes out false."""
    root, _ = later_pr
    done = subprocess.run(
        [sys.executable, "broken.py", "--rehearse-cpu", "--workload",
         "toy-decoder.toy-gamma", "--seed", "2147483777", "--seconds", "3"]
        + ["--break"] * broken,
        cwd=root, capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"},
    )
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    last = done.stderr.strip().splitlines()
    deficit = float(next(l for l in last if l.startswith("compared logit_deficit_max")).split()[2])
    assert result["correct"] is not broken and result["failed"] == 0
    assert (deficit > 0.5) is broken
    assert last[-1].startswith(f"correct: {not broken}")
    assert ('"reference": false' in last[-1]) is broken and '"probes_repeat": true' in last[-1]


# ---------------------------------------------------------------- (c)

# scipy.stats.gamma.ppf(u, shape) (1.17) and, for half-whole shapes, the
# chi-square table: chi2(0.95, 1) / 2, chi2(0.5, 4) / 2, chi2(0.99, 10) / 2
GAMMA_QUANTILES = [
    (1 / 9, 0.5, 0.0011973047159991956), (1 / 9, 0.95, 0.6395818371412935),
    (1 / 9, 0.999, 3.467151112339367), (0.5, 0.95, 1.920729410347062),
    (2.0, 0.5, 1.6783469900166612), (5.0, 0.99, 11.604625579477178),
]


@pytest.mark.parametrize("shape,u,want", GAMMA_QUANTILES)
def test_gamma_quantile_against_known_values(shape, u, want):
    assert traffic.gamma_quantile(shape, u) == pytest.approx(want, rel=1e-11)


@pytest.mark.parametrize("u", [1e-9, 0.003, 0.3, 0.8, 0.9999])
def test_gamma_quantile_of_shape_one_is_the_exponentials(u):
    assert traffic.gamma_quantile(1.0, u) == pytest.approx(-math.log1p(-u), rel=1e-12)
    # shape 1/2 is half a chi-square of one degree: the normal's quantile
    z = statistics.NormalDist().inv_cdf((1 + u) / 2)
    assert traffic.gamma_quantile(0.5, u) == pytest.approx(z * z / 2, rel=1e-9)


def test_gamma_gaps_have_the_mean_and_the_cv_the_mix_states():
    gaps = traffic.gamma_gaps(6.0, 3.0, 10000)
    assert gaps == sorted(gaps) and all(g >= 0 for g in gaps)
    assert statistics.fmean(gaps) == pytest.approx(1 / 6.0, rel=1e-12)
    assert statistics.pstdev(gaps) / statistics.fmean(gaps) == pytest.approx(3.0, rel=0.01)
    # at a cell's own n the tail beyond the last quantile is missing
    cell = traffic.gamma_gaps(6.0, 3.0, 288)
    assert sum(cell) == pytest.approx(48.0, rel=1e-12)
    assert statistics.pstdev(cell) / statistics.fmean(cell) == pytest.approx(2.91, abs=0.01)
    # cv 1 is the Poisson process's gaps
    assert traffic.gamma_gaps(6.0, 1.0, 288) == pytest.approx(traffic.exponential_gaps(6.0, 288), rel=1e-9)


def _mix(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_chat_open_is_due_when_it_was_due_to_the_last_digit():
    dues = traffic.due_times(_mix("chat-open"), 48.0)
    digest = hashlib.sha256(json.dumps([repr(t) for t in dues]).encode()).hexdigest()
    # the parent commit's traffic.py, which knew one open loop
    assert digest == "b54d057e96060b7e47f38cc07e299c59e9e6459c8e273ad122ad085e99717a8b"
    assert [repr(t) for t in dues[:2]] == ["0.13466725093050289", "0.18618513427628058"]
    assert len(dues) == 288 and repr(dues[143]) == "24.606935612079177"
    # "open" with Poisson arrivals named, or none named, is the same schedule
    spelled = {**_mix("chat-open"), "loop": "open"}
    assert traffic.due_times(spelled, 48.0) == dues
    assert traffic.due_times({**spelled, "arrivals": {"process": "poisson"}}, 48.0) == dues


def test_chat_burst_differs_from_chat_open_in_the_arrival_process_alone():
    burst, control = _mix("chat-burst"), _mix("chat-open")
    assert burst["arrivals"] == {"process": "gamma", "cv": 3.0} and burst["loop"] == "open"
    for key in ("route", "rate_per_s", "lead_in_s", "drain_s", "prompt_len",
                "output_len", "temperature"):
        assert burst[key] == control[key], key
    a, b = traffic.due_times(burst, 48.0), traffic.due_times(control, 48.0)
    assert len(a) == len(b) == 288 and a[-1] == pytest.approx(b[-1])
    assert traffic.make_requests(burst, 5, 32768, 288) == traffic.make_requests(control, 5, 32768, 288)
    # every seed the same bunches: 9 arrivals within 10 ms of one another
    gaps = [y - x for x, y in zip([0.0] + a, a)]
    assert max(gaps) == pytest.approx(4.567449, abs=1e-5)
    assert sum(g < 0.010 for g in gaps) > 150 > sum(
        g < 0.010 for g in (y - x for x, y in zip([0.0] + b, b)))


def test_an_unknown_loop_or_process_is_refused():
    with pytest.raises(ValueError):
        traffic.open_loop({"loop": "open-gamma"})
    with pytest.raises(ValueError):
        traffic.due_times({"loop": "open", "rate_per_s": 1.0,
                           "arrivals": {"process": "weibull"}}, 10.0)
    assert traffic.open_loop({"loop": "open"}) and not traffic.open_loop({"loop": "closed"})
