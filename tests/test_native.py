"""Native codec tests: parse/format round trips, equivalence of the fast
JSON paths with the pure-Python decoder, and graceful fallback when the
content is not dense numeric.  Builds the .so (``make native``) into a temp
dir and loads it from there, so a plain local ``pytest`` run exercises the
C++ plane without leaving a binary in the package directory for whoever
copies the tree next; only a missing toolchain skips."""

import json
import shutil
import subprocess
from pathlib import Path

import numpy as np
import pytest

from seldon_core_tpu.contract import (
    Payload,
    payload_from_json,
    payload_to_json,
)
from seldon_core_tpu.contract import native
from seldon_core_tpu.contract.codec import payload_from_dict, payload_to_dict
from seldon_core_tpu.contract.payload import DataKind


@pytest.fixture(scope="module", autouse=True)
def _native_built_in_tmp(tmp_path_factory):
    """Build the codec into a temp dir, serve it to this module, and put
    back whatever the package directory holds (usually nothing) after."""
    repo = Path(__file__).resolve().parent.parent
    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no C++ toolchain to build the native codec")
    out = tmp_path_factory.mktemp("native")
    proc = subprocess.run(
        ["make", "native", f"NATIVE_OUT={out}"],
        cwd=repo, capture_output=True, text=True,
    )
    if proc.returncode != 0:
        # a BROKEN build must fail the suite, not skip it
        pytest.fail(
            f"`make native` failed (rc={proc.returncode}):\n{proc.stderr[-2000:]}"
        )
    if not native.reload(str(out / "libsctcodec.so")):
        pytest.fail("`make native` succeeded but the codec did not load")
    yield
    native.reload()


class TestParseDense:
    def test_2d(self):
        arr, consumed = native.parse_dense(b"[[1,2.5],[3,4e2]]")
        np.testing.assert_allclose(arr, [[1, 2.5], [3, 400.0]])
        assert consumed == len(b"[[1,2.5],[3,4e2]]")

    def test_1d(self):
        arr, _ = native.parse_dense(b"[1,2,3]")
        assert arr.shape == (3,)

    def test_null_becomes_nan(self):
        arr, _ = native.parse_dense(b"[[1,null]]")
        assert np.isnan(arr[0, 1])

    def test_strings_fall_back(self):
        assert native.parse_dense(b'[["a","b"]]') is None

    def test_ragged_falls_back(self):
        assert native.parse_dense(b"[[1,2],[3]]") is None

    def test_deep_nesting_falls_back(self):
        assert native.parse_dense(b"[[[1]]]") is None

    def test_mixed_depth_falls_back(self):
        # scalars at depth 1 mixed with inner rows: not a dense matrix; must
        # fall back, not crash in reshape (n != rows*cols)
        assert native.parse_dense(b"[1.0,[2.0,3.0],[4.0,5.0]]") is None

    def test_consumed_stops_at_bracket(self):
        arr, consumed = native.parse_dense(b'[[1,2]],"names":[]')
        assert consumed == len(b"[[1,2]]")


class TestFormatDense:
    def test_round_trip_exact(self):
        rng = np.random.default_rng(0)
        arr = rng.normal(size=(8, 16)) * 10.0 ** rng.integers(-200, 200, size=(8, 16))
        text = native.format_dense(arr)
        back = np.asarray(json.loads(text))
        np.testing.assert_array_equal(back, arr)  # bit-exact round trip

    def test_nan_inf(self):
        text = native.format_dense(np.array([np.nan, np.inf, -np.inf]))
        assert json.loads(text)[0] is None

    def test_integral_keeps_float_form(self):
        assert native.format_dense(np.array([3.0])) == "[3.0]"


def _big_payload_json(rows=64, cols=32):
    rng = np.random.default_rng(1)
    arr = rng.normal(size=(rows, cols))
    body = {
        "meta": {"puid": "p123", "tags": {"x": 1}},
        "data": {"names": [f"f{i}" for i in range(cols)], "ndarray": arr.tolist()},
    }
    return json.dumps(body), arr


class TestFastJsonPaths:
    def test_from_json_matches_python_path(self):
        raw, arr = _big_payload_json()
        fast = payload_from_json(raw)
        slow = payload_from_dict(json.loads(raw))
        np.testing.assert_allclose(fast.array, slow.array)
        assert fast.meta.puid == "p123" and fast.kind == DataKind.NDARRAY
        assert fast.names == slow.names

    def test_to_json_matches_python_path(self):
        _, arr = _big_payload_json()
        p = Payload.from_array(arr)
        p.meta.puid = "q1"
        fast = json.loads(payload_to_json(p))
        slow = payload_to_dict(p)
        np.testing.assert_allclose(fast["data"]["ndarray"], slow["data"]["ndarray"])
        assert fast["meta"]["puid"] == "q1"

    def test_tensor_kind_to_json(self):
        arr = np.random.default_rng(2).normal(size=(16, 8))
        p = Payload.from_array(arr, kind=DataKind.TENSOR)
        out = json.loads(payload_to_json(p))
        assert out["data"]["tensor"]["shape"] == [16, 8]
        np.testing.assert_allclose(
            np.asarray(out["data"]["tensor"]["values"]).reshape(16, 8), arr
        )

    def test_non_dense_content_falls_back(self):
        body = {"data": {"ndarray": [["a", "b"]] * 200}}
        out = payload_from_json(json.dumps(body))
        assert out.kind == DataKind.NDARRAY
        assert out.array.shape == (200, 2)

    def test_small_payloads_use_python_path(self):
        out = payload_from_json('{"data":{"ndarray":[[1.0,2.0]]}}')
        np.testing.assert_allclose(out.array, [[1.0, 2.0]])

    def test_mixed_depth_wire_input_does_not_crash(self):
        # >=512-byte malformed ndarray body: the native parser must decline
        # so the Python decoder handles it (object array), never ValueError
        rows = ",".join("[2.0,3.0]" for _ in range(100))
        raw = '{"data":{"ndarray":[1.0,%s]}}' % rows
        assert len(raw) >= 512
        out = payload_from_json(raw)
        assert out.kind == DataKind.NDARRAY
        assert out.array.dtype == object

    def test_meta_tag_named_ndarray_does_not_steal_splice(self):
        # a user meta tag literally keyed "ndarray" with null value must not
        # receive the spliced array (meta serializes before data)
        arr = np.random.default_rng(3).normal(size=(64, 16))
        p = Payload.from_array(arr)
        p.meta.tags["ndarray"] = None
        out = json.loads(payload_to_json(p))
        assert out["meta"]["tags"]["ndarray"] is None
        np.testing.assert_allclose(out["data"]["ndarray"], arr.tolist())

    def test_nonstring_names_entry_does_not_steal_splice(self):
        # wire clients may smuggle arbitrary JSON into names; a names entry
        # {"ndarray": null} must not receive the spliced array
        arr = np.random.default_rng(5).normal(size=(64, 16))
        p = Payload.from_array(arr)
        p.names = [{"ndarray": None}]
        out = json.loads(payload_to_json(p))
        assert out["data"]["names"] == [{"ndarray": None}]
        np.testing.assert_allclose(out["data"]["ndarray"], arr.tolist())

    def test_meta_tag_named_values_does_not_steal_tensor_splice(self):
        arr = np.random.default_rng(4).normal(size=(32, 16))
        p = Payload.from_array(arr, kind=DataKind.TENSOR)
        p.meta.tags["values"] = None
        out = json.loads(payload_to_json(p))
        assert out["meta"]["tags"]["values"] is None
        np.testing.assert_allclose(
            np.asarray(out["data"]["tensor"]["values"]).reshape(32, 16), arr
        )
