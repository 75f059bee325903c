"""The program's host spans (``repro.utils.spans``) in a profiler trace.

One traced ``Executor.decompose`` per case: the ``receipt.*`` spans nest
``decompose ⊃ plan`` and ``decompose ⊃ engine ⊃ {cd, fd}`` (or
``⊃ tiled``), every blocking transfer is a ``receipt.sync`` event counted
once in ``RunStats.host_round_trips``, the spans close, and tracing leaves
the result unchanged.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import EngineConfig, Executor
from repro.core.graph import powerlaw_bipartite
from repro.core.peeling import butterfly_supports

SMALL_BLOCKS = (8, 8, 8)


def _executor(**kw):
    base = dict(num_partitions=6, kernel_blocks=SMALL_BLOCKS, backend="xla",
                representation="dense")
    base.update(kw)
    return Executor(EngineConfig(**base))


def _graph():
    return powerlaw_bipartite(120, 70, 800, seed=7)


def _spans(log_dir):
    """``(thread, start_ns, end_ns, name, metadata)`` of every
    ``receipt.*`` event on the host plane, the prefix dropped."""
    files = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("receipt."):
                    out.append((thread, e.start_ns, e.end_ns,
                                e.name[len("receipt."):], dict(e.stats)))
    return out


def _named(spans, name):
    return [s for s in spans if s[3] == name]


def _inside(inner, outer):
    return (inner[0] == outer[0] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


def _traced(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        result = fn()
    return result, _spans(tmp_path)


def _check_closed(spans, outer):
    """Every span of the call lies inside its outermost span: none was
    left open (an open span is never written) or escaped its parent."""
    assert all(_inside(s, outer) for s in spans), spans


@pytest.mark.parametrize("kw, phases", [
    (dict(cd_dispatch="subset"), ("cd", "fd")),
    (dict(cd_dispatch="graph"), ("cd", "fd")),
    (dict(fd_overlap=False), ("cd", "fd")),
    (dict(representation="tiled"), ("tiled",)),
    (dict(workload="wing"), ("cd", "fd")),
], ids=["dense-subset", "dense-graph", "no-overlap", "tiled", "wing"])
def test_decompose_spans_nest_and_count_every_sync(tmp_path, kw, phases):
    g = _graph()
    plain = _executor(**kw).decompose(g)
    dec, spans = _traced(tmp_path, lambda: _executor(**kw).decompose(g))

    np.testing.assert_array_equal(dec.numbers, plain.numbers)
    (top,) = _named(spans, "decompose")
    assert top[4] == {"run": 1}
    _check_closed(spans, top)
    (plan,) = _named(spans, "plan")
    (engine,) = _named(spans, "engine")
    assert _inside(plan, top) and _inside(engine, top)
    assert engine[4] == {"backend": "xla"}
    for phase in phases:
        (span,) = _named(spans, phase)
        assert _inside(span, engine), phase
    for other in {"cd", "fd", "tiled"} - set(phases):
        assert not _named(spans, other), other

    syncs = _named(spans, "sync")
    assert len(syncs) == dec.stats.host_round_trips > 0
    assert all(_inside(s, engine) and s[4].get("phase") for s in syncs)
    assert dec.stats.time_cd + dec.stats.time_fd > 0


def test_subset_dispatch_spans_each_subset_and_device_graph(tmp_path):
    dec, spans = _traced(
        tmp_path, lambda: _executor(cd_dispatch="subset").decompose(_graph()))
    (cd,) = _named(spans, "cd")
    subsets = _named(spans, "cd.subset")
    assert [s[4]["i"] for s in subsets] == list(range(dec.stats.num_subsets))
    graphs = _named(spans, "cd.graph")
    assert len(graphs) == 1 + dec.stats.dgm_compactions
    (count,) = _named(spans, "cd.count")
    for s in subsets + graphs + [count]:
        assert _inside(s, cd)
    (fd,) = _named(spans, "fd")
    stacks = _named(spans, "fd.stack")
    assert len(stacks) == len(_named(spans, "fd.launch")) \
        == dec.stats.fd_groups
    assert all(s[4]["bytes"] > 0 and _inside(s, fd) for s in stacks)
    for name in ("fd.tasks", "fd.prepeel"):
        (s,) = _named(spans, name)
        assert _inside(s, fd)
    # the CD wall timer is the cd span, counting included
    assert dec.stats.time_count + dec.stats.time_cd == pytest.approx(
        (cd[2] - cd[1]) / 1e9, rel=0.05, abs=2e-3)


def test_device_graph_span_carries_its_edges_and_upload_bytes(tmp_path):
    """Each ``cd.graph`` build reports its padded edge count and the bytes
    it moved to the device; those sum to ``RunStats.cd_graph_upload_bytes``,
    and each is the int32 edge list plus ``dv0``, ``w``, ``row_ext`` and
    ``kmax``, never the dense matrix."""
    dec, spans = _traced(
        tmp_path, lambda: _executor(cd_dispatch="subset").decompose(_graph()))
    graphs = _named(spans, "cd.graph")
    assert len(graphs) == 1 + dec.stats.dgm_compactions > 1
    for s in graphs:
        meta = s[4]
        assert meta["edges"] >= 1024
        rows = meta["rows"]
        assert meta["upload_bytes"] == 8 * meta["edges"] + 4 * meta["cols"] \
            + 8 * rows + 4 * rows // SMALL_BLOCKS[0]
    assert sum(s[4]["upload_bytes"] for s in graphs) \
        == dec.stats.cd_graph_upload_bytes


def test_prepeel_span_carries_its_pairs(tmp_path):
    """``fd.prepeel`` reports the pairs its host level deltas traversed,
    the same count as ``RunStats.fd_prepeel_pairs``."""
    dec, spans = _traced(tmp_path, lambda: _executor().decompose(_graph()))
    (prepeel,) = _named(spans, "fd.prepeel")
    assert prepeel[4]["levels"] == 4
    assert prepeel[4]["pairs"] == dec.stats.fd_prepeel_pairs > 0


def test_repeel_counts_its_syncs(tmp_path):
    g = _graph()
    ex = _executor()
    full = ex.decompose(g)
    sup0 = butterfly_supports(g).astype(np.float64)
    kwargs = dict(sup0=sup0, numbers_old=full.numbers, stops=[np.inf],
                  watch=np.zeros(0, np.int64))
    (numbers, stats), spans = _traced(tmp_path,
                                      lambda: ex.repeel(g, **kwargs))

    np.testing.assert_array_equal(numbers, full.numbers)
    (top,) = _named(spans, "repeel")
    assert top[4] == {"run": 2}
    _check_closed(spans, top)
    syncs = _named(spans, "sync")
    assert len(syncs) == stats.host_round_trips > 0
    assert {s[4]["phase"] for s in syncs} == {"refresh"}
