"""The four-device deployment through the normal path (subprocess with
forced host devices).

``Executor(EngineConfig(side="U"), mesh=<4-device mesh>)`` is the
configuration the four-chip benchmark cell runs: CD on one device of the
mesh, FD's independent subsets LPT-sharded over all four.  jax fixes the
device count at its first initialisation, so the runs happen in a fresh
interpreter with ``--xla_force_host_platform_device_count=8`` and report
back as JSON.
"""
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
import repro.core.distributed as dist
import repro.core.engine as engine
import repro.core.engine.fd as fd
from repro.api import EngineConfig, Executor
from repro.core.graph import powerlaw_bipartite
from repro.core.peeling import bup_oracle
from repro.launch.mesh import make_mesh
from repro.utils.spans import span

opened = []


class Recording(span):
    def __init__(self, name, **meta):
        super().__init__(name, **meta)
        self.record = dict(meta, name=name)
        opened.append(self.record)

    def set_metadata(self, **meta):
        self.record.update(meta)
        super().set_metadata(**meta)


laid_out = []
shard_level_group = dist.shard_level_group


def recording_layout(built, n_shards, init_loads=None):
    arrays, slots = shard_level_group(built, n_shards, init_loads)
    laid_out.append(int(arrays["a"].nbytes + arrays["a_l1"].nbytes))
    return arrays, slots


cd_on = []
receipt_cd = engine.receipt_cd


def recording_cd(*args, **kw):
    cd_on.append(jnp.zeros(()).devices().pop().id)
    return receipt_cd(*args, **kw)


graph_on = []
DeviceGraph = engine.cd.DeviceGraph


class RecordingGraph(DeviceGraph):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        graph_on.append(sorted(d.id for d in self.a.devices()))


fd.span = Recording
dist.shard_level_group = recording_layout
engine.receipt_cd = recording_cd
engine.cd.DeviceGraph = RecordingGraph

g = powerlaw_bipartite(512, 256, 4000, seed=5)
oracle, _ = bup_oracle(g)
one = Executor(EngineConfig(side="U")).decompose(g)
cd_one = list(cd_on)
graph_one = list(graph_on)
del opened[:], laid_out[:], cd_on[:], graph_on[:]

dec = Executor(EngineConfig(side="U"),
               mesh=make_mesh((4,), ("data",))).decompose(g)
s = dec.stats
out = {
    "n_edges": int(g.m),
    "mesh_equals_oracle": bool((dec.theta == oracle).all()),
    "mesh_equals_one_device": bool((dec.theta == one.theta).all()),
    "one_device_equals_oracle": bool((one.theta == oracle).all()),
    "mesh_shards": dec.plan.mesh_shards,
    "representation": dec.plan.representation,
    "fd_shards": s.fd_shards,
    "fd_shard_rho": s.fd_shard_rho,
    "fd_shard_bytes": s.fd_shard_bytes,
    "fd_groups": s.fd_groups,
    "shard_spans": [r for r in opened if r["name"] == "fd.shard"],
    "launch_spans": sum(r["name"] == "fd.launch" for r in opened),
    "laid_out_bytes": list(laid_out),
    "cd_device_one": cd_one,
    "cd_device_mesh": list(cd_on),
    "graph_devices_one": graph_one,
    "graph_devices_mesh": list(graph_on),
}

del cd_on[:], graph_on[:]
far = make_mesh((4,), ("data",), devices=jax.devices()[4:8])
dec_far = Executor(EngineConfig(side="U"), mesh=far).decompose(g)
out.update(
    far_mesh_devices=sorted(d.id for d in far.devices.flat),
    cd_device_far=list(cd_on),
    graph_devices_far=list(graph_on),
    far_equals_oracle=bool((dec_far.theta == oracle).all()),
    far_fd_shards=dec_far.stats.fd_shards,
)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def run():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_mesh_decomposition_equals_oracle_and_one_device(run):
    assert run["n_edges"] > 2500
    assert run["one_device_equals_oracle"]
    assert run["mesh_equals_oracle"]
    assert run["mesh_equals_one_device"]


def test_mesh_plan_is_dense_and_every_shard_peels(run):
    assert run["mesh_shards"] == 4
    assert run["representation"] == "dense"
    assert run["fd_shards"] == 4
    assert len(run["fd_shard_rho"]) == 4
    assert all(r > 0 for r in run["fd_shard_rho"])


def test_fd_shard_span_opens_once_per_group_with_the_placed_bytes(run):
    shard_spans = run["shard_spans"]
    assert run["fd_groups"] > 1
    assert len(shard_spans) == run["fd_groups"] == run["launch_spans"]
    assert all(sp["shards"] == 4 for sp in shard_spans)
    # the bytes each group uploads are the stacks the LPT layout built
    assert [sp["bytes"] for sp in shard_spans] == run["laid_out_bytes"]
    for sp in shard_spans:
        # contiguous equal-size shards: each device gets a quarter
        assert sp["max_shard_bytes"] * 4 == sp["bytes"]
    assert len(run["fd_shard_bytes"]) == 4
    assert sum(run["fd_shard_bytes"]) == sum(run["laid_out_bytes"])


def test_cd_runs_on_a_device_of_the_mesh(run):
    assert run["cd_device_one"] == run["cd_device_mesh"] == [0]
    assert run["far_mesh_devices"] == [4, 5, 6, 7]
    assert run["cd_device_far"] == [4]
    assert run["far_equals_oracle"]
    assert run["far_fd_shards"] == 4


def test_cd_residual_graph_is_built_on_the_cd_device(run):
    """Every ``DeviceGraph`` build, DGM boundaries included, lands its
    dense matrix on the chip CD runs on, never on JAX's global default."""
    for key in ("graph_devices_one", "graph_devices_mesh"):
        assert len(run[key]) > 1, key
        assert run[key] == [[0]] * len(run[key]), key
    assert len(run["graph_devices_far"]) > 1
    assert run["graph_devices_far"] == [[4]] * len(run["graph_devices_far"])
