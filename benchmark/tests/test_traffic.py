import types

import pytest

import loadgen
import metriclib
import stats
import traffic

CHAT = {
    "route": "stream", "loop": "open-poisson", "rate_per_s": 8.0,
    "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 16, "max": 1536},
    "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7, "min": 16, "max": 448},
}


def test_every_seed_gets_the_same_work_at_the_same_instants():
    a = traffic.due_times(CHAT, 38.0)
    assert a == traffic.due_times(CHAT, 38.0) and len(a) == 304
    assert a[-1] == pytest.approx(38.0)
    gaps = sorted(y - x for x, y in zip([0.0] + a, a))
    assert gaps == pytest.approx(sorted(traffic.exponential_gaps(8.0, 304)))
    ra = traffic.make_requests(CHAT, 2**31 + 5, 32768, 304)
    rb = traffic.make_requests(CHAT, 7, 32768, 304)
    assert ra == traffic.make_requests(CHAT, 2**31 + 5, 32768, 304)
    # the same sizes in the same order; other token ids
    assert [len(r["tokens"]) for r in ra] == [len(r["tokens"]) for r in rb]
    assert [r["max_new"] for r in ra] == [r["max_new"] for r in rb]
    assert ra[0]["tokens"] != rb[0]["tokens"]
    lens = [len(r["tokens"]) for r in ra]
    assert lens != sorted(lens) and sorted(lens) == traffic.stratified(CHAT["prompt_len"], 304)
    assert min(lens) >= 16 and max(lens) <= 1536
    assert all(1 <= t < 32768 for r in ra for t in r["tokens"])
    assert len({tuple(r["tokens"]) for r in ra}) == 304  # every prompt unique


def test_choice_keeps_its_weights():
    rows = traffic.stratified(
        {"dist": "choice", "values": [1, 4, 16, 64], "weights": [0.4, 0.3, 0.2, 0.1]}, 1000
    )
    assert [rows.count(v) for v in (1, 4, 16, 64)] == [400, 300, 200, 100]


def test_latency_counts_from_the_due_instant_when_the_sender_is_late():
    s = loadgen.Sample(index=0, due=10.0, sent=10.5, first=11.0, done=13.0,
                       asked=201, got=201, ok=True)
    run = types.SimpleNamespace(mix={"route": "stream"}, counted=[s], stats=stats)
    assert metriclib.ttft_ms(run) == [pytest.approx(1000.0)]  # not 500
    assert metriclib.late_ms(run) == [pytest.approx(500.0)]
    assert metriclib.tpot_ms(run) == [pytest.approx(10.0)]


def test_tokens_count_where_they_arrive_not_where_they_were_asked():
    # blocks of 16 at 0.9, 1.1, 1.5 and 2.5 s; the window is [1.0, 2.0)
    s = loadgen.Sample(index=0, due=0.0, sent=0.0, ok=True,
                       token_times=[(0.9, 16), (1.1, 16), (1.5, 16), (2.5, 16)])
    bad = loadgen.Sample(index=1, due=0.0, sent=0.0, ok=False, token_times=[(1.5, 16)])
    run = types.SimpleNamespace(samples=[s, bad], w0=1.0, w1=2.0)
    # made over (0.9, 1.1]: half inside; (1.1, 1.5]: whole; (1.5, 2.5]: half
    assert metriclib.tokens_in_window(run) == pytest.approx(8 + 16 + 8)
    first = loadgen.Sample(index=2, due=0.0, sent=0.0, ok=True, token_times=[(1.2, 16)])
    run.samples = [first]
    assert metriclib.tokens_in_window(run) == 16  # a first block: at arrival


def test_a_blocks_events_are_one_block_however_the_client_stamped_them():
    # the client stamps every SSE event: a block's 16 events lie
    # microseconds apart, blocks 0.2 s apart; the window is [1.0, 2.0)
    def events(t):
        return [(t - 30e-6 * (15 - i), 1) for i in range(16)]

    per_event = loadgen.Sample(
        index=0, due=0.0, sent=0.0, ok=True,
        token_times=[(0.7, 1)] + events(0.9) + events(1.1) + events(1.5) + events(2.5),
    )
    blocks = metriclib.blocks_of(per_event.token_times)
    assert [n for _, n in blocks] == [1, 16, 16, 16, 16]
    assert [t for t, _ in blocks] == pytest.approx([0.7, 0.9, 1.1, 1.5, 2.5])
    run = types.SimpleNamespace(samples=[per_event], w0=1.0, w1=2.0)
    # as the block-stamped stream above: half + whole + half, not 16 + 16
    assert metriclib.tokens_in_window(run) == pytest.approx(8 + 16 + 8)
    # steps of a decode_block 1 engine lie a step apart: never joined
    single = [(1.0 + 0.0075 * i, 1) for i in range(4)]
    assert len(metriclib.blocks_of(single)) == 4
