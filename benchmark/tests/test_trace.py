"""The trace reduction against a small recorded trace: three decode blocks
(two of window 256, one of window 512) cut from a ``--trace 1`` run of
``mistral-7b-l8.decode-closed`` on a TPU v5e (PR 23; device plane's ``XLA
Modules`` and ``XLA Ops`` lines and the host's dispatch annotations kept,
operation names cut to 160 characters)."""

import os

import pytest

import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "decode_closed.cut.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(FIXTURE))


def test_programs_take_the_annotation_of_their_dispatch(reduced):
    progs = reduced["programs"]
    assert [p["label"] for p in progs] == [
        "decode_k:k16:w256", "decode_k:k16:w256", "decode_k:k16:w512",
    ]
    assert progs[0]["module"] == progs[1]["module"] != progs[2]["module"]
    # device time of a 16-step block: 219.2 ms at window 256, 228.8 at 512
    assert [round(p["device_s"] * 1e3, 1) for p in progs] == [219.2, 219.2, 228.8]


def test_busy_union_window_and_idle(reduced):
    # the operations of a block run back to back: busy is the three blocks
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(0.2192 + 0.2192 + 0.2288, abs=2e-4)
    assert reduced["window_s"] == pytest.approx(0.68740, abs=1e-4)
    idle = dict(reduced["breakdown"]["idle_gaps"])
    # two gaps between blocks, about 10 ms each: the host's turn-round
    assert idle["before:decode_k:k16:w256"] == pytest.approx(0.01046, abs=1e-4)
    assert idle["before:decode_k:k16:w512"] == pytest.approx(0.00972, abs=1e-4)
    assert sum(idle.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-6
    )
    assert reduced["collective_s"] == 0


def test_device_ops_are_named_by_operation_and_leave_containers_out(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert len(ops) == 10
    assert ops[0][0] == "dynamic-slice_bitcast_fusion.5"
    assert not any(name.startswith("while") for name, _ in ops)
    assert all(len(name) < 64 and " " not in name for name, _ in ops)


def test_op_name():
    assert trace.op_name("%fusion.219 = bf16[32,14336]{1,0} fusion(bf16[...] %x)") == "fusion.219"
    assert trace.op_name("jit_fn(123)") == "jit_fn(123)"
