"""The trace reduction on a small trace recorded on a TPU v5e
(``record_trace.py``): one ``butterfly_support_pallas`` call, a 50 ms
host span with the device idle, then a jnp product."""
import pathlib

import pytest

from benchmarks.chip import trace

TRACE = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(TRACE))


def test_window_and_busy_time(reduced):
    assert reduced["devices"] == 1
    # the window spans two 20 ms sleeps and the 50 ms span
    assert 0.09 < reduced["window_s"] < 1.0
    assert 0 < reduced["busy_s"] < 0.01 * reduced["window_s"]


def test_kernel_is_found_by_name(reduced):
    assert "butterfly_support_pallas" in reduced["custom_calls"]
    op = reduced["ops"]["butterfly_support_pallas"]
    assert op["count"] == 1 and 0 < op["s"] < reduced["busy_s"]
    assert trace.kernel_seconds(reduced) == op["s"]


def test_longest_idle_gap_is_named_by_the_open_host_span(reduced):
    label, seconds = reduced["idle_gaps"][0]
    assert label == "bench.sleep"
    assert 0.05 <= seconds < 0.2


def test_breakdown_holds_self_times(reduced):
    names = [n for n, _s in reduced["device_ops"]]
    assert "butterfly_support_pallas" in names
    assert all(s >= 0 for _n, s in reduced["device_ops"])
    assert sum(s for _n, s in reduced["device_ops"]) <= reduced["busy_s"]


def test_op_names_drop_the_instruction_suffix():
    name = ("%butterfly_support_pallas.5 = f32[1,16384]{1,0:T(1,128)} "
            "custom-call(f32[16384,8192]{1,0:T(8,128)} %p)")
    assert trace.op_name(name) == "butterfly_support_pallas"
    assert trace.is_custom_call(name)
