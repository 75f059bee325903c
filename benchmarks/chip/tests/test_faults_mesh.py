"""``correct`` comes out false when the four-chip path is broken underneath.

The cell ``static-dense.mesh4`` runs on four chips, so here it runs on
four forced host devices, in a fresh interpreter (jax fixes the device
count at its first initialisation), at the tiny size of
``test_faults.py``.  Faults planted in the program: one shard's tip
numbers dropped where the mesh FD path writes them back (the exchange
between chips that the one-chip cell has none of), and a mesh of two
devices where the configuration states four.  The sound run must come
out correct.
"""
import json
import pathlib
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip import spans, spec, trace

ROOT = pathlib.Path(__file__).resolve().parents[3]
TRACE = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"
READERS = ("shard_imbalance.mesh4", "fd_shard_ms.mesh4", "idle_cd_ms.mesh4",
           "idle_fd_ms.mesh4")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import copy, json, sys
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
import jax
from benchmarks.chip import spec
from benchmarks.chip.run import Run, execute
import repro.core.distributed as dist

ROOT = __import__("pathlib").Path(sys.argv[1])


def run_cell(devices=4, mesh_shape=None):
    cell = spec.load_cell("static-dense.mesh4", ROOT)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.traffic["graph"].update({"n_u": 256, "n_v": 128, "m_target": 1200})
    if mesh_shape is not None:
        cell.config = copy.deepcopy(cell.config)
        cell.config["mesh"]["shape"] = mesh_shape
    run = Run(cell, 2**31 + 77, 2.0, jax.devices()[:devices],
              backends=("xla",))
    run.listen()
    out = execute(run, spec.kind_module(cell.traffic["kind"]), traced=False)
    return {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"],
            "checks": {n: [v, lim] for n, v, lim in out["checks"]}}


shard_level_group = dist.shard_level_group


def drop_second_shard(built, n_shards, init_loads=None):
    arrays, slots = shard_level_group(built, n_shards, init_loads)
    per = arrays["per_shard"]
    slots = slots.copy()
    slots[per:2 * per] = -1          # its tip numbers are never written back
    return arrays, slots


got = {"sound": run_cell(), "two_devices": run_cell(2, [2])}
dist.shard_level_group = drop_second_shard
got["shard_dropped"] = run_cell()
print(json.dumps(got))
"""


@pytest.fixture(scope="module")
def runs():
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT)],
                       capture_output=True, text=True, timeout=900,
                       cwd=ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def failed_checks(out):
    return {n for n, (v, lim) in out["checks"].items() if v > lim}


def test_mesh_sound_run_is_correct(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["shard_mismatch"] == [0, 0]


def test_mesh_dropped_shard_is_caught(runs):
    out = runs["shard_dropped"]
    assert not out["correct"]
    assert "theta_mismatch" in failed_checks(out)


def test_mesh_of_two_devices_is_caught(runs):
    out = runs["two_devices"]
    assert not out["correct"]
    assert failed_checks(out) == {"shard_mismatch"}


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(spans, "_PARSED", {})
    monkeypatch.setattr(spans, "_SUMMARIES", {})
    return tmp_path


def _read(ctx):
    return {name: spec.reader(name)(ctx) for name in READERS}


MS = 1e6                                 # ns


def _two_device_run(trace_dir, names):
    """One 1 s window on two devices (busy 100-200 ms and 600-700 ms)
    with the host spans ``names`` ((start ms, end ms, name))."""
    path = trace_dir / "run.xplane.pb"
    path.write_bytes(b"")
    spans._PARSED[str(path)] = spans.Parsed(
        (0.0, 1000 * MS), [[(100 * MS, 200 * MS)], [(600 * MS, 700 * MS)]],
        [spans.Span(s * MS, e * MS, "receipt." + n, 0, "")
         for s, e, n in names])
    return {"trace": {"window_s": 1.0}, "decompositions": [{}, {}]}


def test_mesh_readers_without_their_span_or_counter_read_nothing(trace_dir):
    # the parent's program: no receipt.fd.shard span, no shard counters
    ctx = _two_device_run(trace_dir, [(0, 500, "cd"), (500, 1000, "fd")])
    got = _read(ctx)
    assert got["shard_imbalance.mesh4"] is None
    assert got["fd_shard_ms.mesh4"] is None
    # idle under cd: 400 and 500 ms on the two devices, under fd 500 and
    # 400 ms; the mean over the devices, per decomposition
    assert got["idle_cd_ms.mesh4"] == pytest.approx(225.0)
    assert got["idle_fd_ms.mesh4"] == pytest.approx(225.0)


def test_fd_shard_ms_reads_the_host_time_of_its_spans(trace_dir):
    ctx = _two_device_run(trace_dir, [(0, 500, "cd"), (500, 1000, "fd"),
                                      (510, 530, "fd.shard"),
                                      (700, 740, "fd.shard")])
    assert spec.reader("fd_shard_ms.mesh4")(ctx) == pytest.approx(30.0)


def test_mesh_readers_on_a_trace_without_program_spans(trace_dir):
    shutil.copy(TRACE, trace_dir / "small.xplane.pb")
    ctx = {"trace": trace.reduce(str(TRACE)), "decompositions": [{}]}
    assert set(_read(ctx).values()) == {None}


def test_shard_imbalance_is_the_largest_load_over_the_mean():
    runs = [{"fd_shard_wedges": [4.0, 2.0, 1.0, 1.0]},
            {"fd_shard_wedges": [1.0, 1.0, 1.0, 1.0]}]
    got = spec.reader("shard_imbalance.mesh4")({"decompositions": runs})
    assert got == pytest.approx((2.0 + 1.0) / 2)
