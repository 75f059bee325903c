"""The attribution of device-idle time to the program's ``receipt.*`` host
spans (``spans.py``) and the per-layer readers built on it."""
import pathlib
import shutil

import pytest

from benchmarks.chip import spans, spec, trace

TRACE = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"
MS = 1e6                                 # ns
READERS = ("plan_ms.static", "idle_cd_ms.static", "idle_fd_ms.static",
           "idle_unattributed.static")

# a 1 s window; the device runs 150-300 ms (two overlapping programs),
# 600-650 ms, and from 1050 ms past the window's end; a program long
# before the window is left out.  One decomposition's spans nest on
# thread 0, and its fd span (with engine and decompose) straddles the
# window's end.
WINDOW = (100 * MS, 1100 * MS)
DEVICE = [(10 * MS, 20 * MS), (150 * MS, 200 * MS), (180 * MS, 300 * MS),
          (600 * MS, 650 * MS), (1050 * MS, 1200 * MS)]
SPANS = [spans.Span(s * MS, e * MS, "receipt." + n, 0, ph)
         for s, e, n, ph in [
             (110, 1150, "decompose", ""),
             (110, 130, "plan", ""),
             (130, 1150, "engine", ""),
             (130, 500, "cd", ""),
             (140, 400, "cd.subset", ""),
             (300, 310, "sync", "cd.subset"),
             (500, 1150, "fd", ""),
         ]]


def _busy_as_reduce_computes_it(window, device):
    lo, hi = window[0] - trace.CLOCK_SKEW_NS, window[1]
    return sum(e - s for s, e in trace._union(trace._clip(device, lo, hi)))


def test_idle_under_the_phases_adds_up_to_the_window_less_busy():
    found = spans.attribute(WINDOW, [DEVICE], SPANS)
    names = found["names"]
    idle = {n[len("receipt."):]: v["idle_s"] for n, v in names.items()}
    assert idle["plan"] == pytest.approx(0.020)
    assert idle["cd"] == pytest.approx(0.220)     # 130-150, 300-500
    assert idle["fd"] == pytest.approx(0.500)     # 500-600, 650-1050
    assert idle["sync"] == pytest.approx(0.010)   # 300-310
    assert found["unattributed_s"] == pytest.approx(0.010)   # 100-110

    window_ns = WINDOW[1] - WINDOW[0]
    busy_ns = _busy_as_reduce_computes_it(WINDOW, DEVICE)
    assert busy_ns == 250 * MS
    phases = sum(v["idle_s"] for n, v in names.items() if n in spans.PHASES)
    assert phases + found["unattributed_s"] == pytest.approx(
        (window_ns - busy_ns) / 1e9, rel=1e-12)
    assert found["idle_s"] == pytest.approx((window_ns - busy_ns) / 1e9,
                                            rel=1e-12)


def test_host_and_self_times_are_clipped_to_the_window():
    names = spans.attribute(WINDOW, [DEVICE], SPANS)["names"]
    fd = names["receipt.fd"]
    assert fd["count"] == 1 and fd["host_s"] == pytest.approx(0.600)
    assert names["receipt.engine"]["self_s"] == pytest.approx(0.0)
    assert names["receipt.cd"]["self_s"] == pytest.approx(0.110)
    assert names["receipt.cd.subset"]["self_s"] == pytest.approx(0.250)


def test_no_device_program_reads_nothing():
    assert spans.attribute(WINDOW, [[(10 * MS, 20 * MS)]], SPANS) is None


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(spans, "_PARSED", {})
    monkeypatch.setattr(spans, "_SUMMARIES", {})
    (tmp_path / "cell").mkdir()
    return tmp_path / "cell"


def _read(ctx):
    return {name: spec.reader(name)(ctx) for name in READERS}


def test_readers_on_a_run_with_spans(trace_dir):
    path = trace_dir / "run.xplane.pb"
    path.write_bytes(b"")
    spans._PARSED[str(path)] = spans.Parsed(WINDOW, [DEVICE], SPANS)
    ctx = {"trace": {"window_s": 1.0}, "decompositions": [{}, {}]}
    got = _read(ctx)
    assert got["plan_ms.static"] == pytest.approx(10.0)
    assert got["idle_cd_ms.static"] == pytest.approx(110.0)
    assert got["idle_fd_ms.static"] == pytest.approx(250.0)
    assert got["idle_unattributed.static"] == pytest.approx(10 / 750 * 100)
    # another run's window: nothing to read
    ctx["trace"]["window_s"] = 1.002
    assert set(_read(ctx).values()) == {None}


def test_readers_on_a_trace_without_program_spans(trace_dir, capsys):
    shutil.copy(TRACE, trace_dir / "small.xplane.pb")
    reduced = trace.reduce(str(TRACE))
    ctx = {"trace": reduced, "decompositions": [{}]}
    assert set(_read(ctx).values()) == {None}
    assert spans.main([str(TRACE)]) == 0
    assert "window" in capsys.readouterr().out
