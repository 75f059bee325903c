"""The repro.api plan/compile/execute service layer (PR 5 tentpole):
EngineConfig validation + serialization, Planner/ExecutionPlan fields,
the Executor's cross-graph executable cache (trace-counter-asserted),
and multi-graph batched decomposition (Executor.map)."""
import dataclasses

import numpy as np
import pytest

from repro.api import (
    EngineConfig,
    Executor,
    Planner,
    TipDecomposition,
    decompose,
)
from repro.core.graph import BipartiteGraph, powerlaw_bipartite
from repro.core.peeling import bup_oracle
from repro.core.receipt import ReceiptConfig, tip_decompose

from conftest import GRAPH_CASES

SMALL_BLOCKS = (8, 8, 8)


def _cfg(**kw):
    base = dict(num_partitions=6, kernel_blocks=SMALL_BLOCKS, backend="xla")
    base.update(kw)
    return EngineConfig(**base)


def _permuted_copy(g: BipartiteGraph, seed: int) -> BipartiteGraph:
    """An isomorphic copy (rows and cols relabeled): same bucketed shape,
    same support/wedge multisets — the executable cache's home turf."""
    rng = np.random.default_rng(seed)
    pu = rng.permutation(g.n_u)
    pv = rng.permutation(g.n_v)
    return BipartiteGraph.from_edges(g.n_u, g.n_v, pu[g.edges_u],
                                     pv[g.edges_v])


# --------------------------------------------------------------------- #
# EngineConfig: strict validation + serialization round trip
# --------------------------------------------------------------------- #
def test_engine_config_roundtrip():
    cfg = _cfg(num_partitions=12, side="V", cd_dispatch="graph",
               fd_update_mode="kernel", peel_width=32)
    assert EngineConfig.from_dict(cfg.to_dict()) == cfg
    # to_dict is JSON-able (tuples become lists)
    import json

    assert json.loads(json.dumps(cfg.to_dict())) == cfg.to_dict()


def test_engine_config_rejects_unknown_keys_with_hint():
    d = _cfg().to_dict()
    d["num_partition"] = 4                       # typo'd knob
    with pytest.raises(ValueError, match="num_partitions"):
        EngineConfig.from_dict(d)
    with pytest.raises(ValueError, match="unknown key"):
        EngineConfig.from_dict({"definitely_not_a_knob": 1})


@pytest.mark.parametrize("bad", [
    dict(side="W"),
    dict(dtype="float64"),
    dict(backend="palas"),                       # typo: actionable error
    dict(representation="sparse"),
    dict(cd_dispatch="Graph"),
    dict(num_partitions=0),
    dict(max_sweeps=0),
    dict(peel_width=0),
    dict(dgm_row_threshold=0.0),
    dict(fd_update_mode="fast"),
    dict(kernel_blocks=(8, 8)),
])
def test_engine_config_rejects_bad_values(bad):
    with pytest.raises(ValueError):
        _cfg(**bad)


def test_engine_config_rejects_conflicting_knobs():
    """Cross-knob conflicts that RUN but silently diverge from the
    benched configuration are service-layer errors (the raw
    ReceiptConfig keeps permitting them for A/B tests)."""
    with pytest.raises(ValueError, match="use_dgm"):
        _cfg(cd_dispatch="graph", use_dgm=False)
    # the engine-layer config allows the A/B combination
    ReceiptConfig(cd_dispatch="graph", use_dgm=False)


def test_receipt_config_validates_at_construction():
    """The legacy config's validation gaps are closed: a typo'd backend
    used to silently route to the compiled pallas kernel."""
    with pytest.raises(ValueError, match="backend"):
        ReceiptConfig(backend="palas")
    with pytest.raises(ValueError, match="dgm_row_threshold"):
        ReceiptConfig(dgm_row_threshold=1.5)
    with pytest.raises(ValueError, match="square"):
        ReceiptConfig(backend="pallas_sparse", kernel_blocks=(8, 16, 8))


# --------------------------------------------------------------------- #
# Planner / ExecutionPlan
# --------------------------------------------------------------------- #
def test_plan_surfaces_execution_structure():
    g = GRAPH_CASES["powerlaw"]()
    plan = Planner(_cfg(num_partitions=8)).plan(g)
    assert plan.rows_pad >= g.n_u and plan.rows_pad % 8 == 0
    assert plan.cols_pad % 8 == 0
    assert plan.backend == "xla" and "oracle" in plan.kernel_route
    assert plan.cd_dispatch == "subset"
    assert plan.num_partitions == 8
    assert plan.cd_peel_width0 >= 8
    assert plan.est_fd_groups, "planner must estimate FD shape groups"
    assert all(g_["rows"] % 8 == 0 for g_ in plan.est_fd_groups)
    assert 0.0 <= plan.est_fd_padding_waste < 1.0
    assert plan.padded_bytes > 0
    assert plan.mesh_shards == 0
    assert isinstance(plan.describe(), str) and "CD" in plan.describe()
    d = plan.to_dict()
    assert d["rows_pad"] == plan.rows_pad


def test_plan_signature_keys_on_bucketed_shape_and_config():
    p = Planner(_cfg())
    g1 = powerlaw_bipartite(100, 60, 700, seed=0)
    g2 = powerlaw_bipartite(101, 60, 700, seed=3)     # same buckets
    g3 = powerlaw_bipartite(400, 60, 700, seed=0)     # different bucket
    assert p.plan(g1).signature == p.plan(g2).signature
    assert p.plan(g1).signature != p.plan(g3).signature
    assert p.plan(g1).signature != Planner(
        _cfg(num_partitions=12)).plan(g1).signature


def test_planner_rejects_non_graph_with_ingestion_hint():
    with pytest.raises(ValueError, match="from_edges"):
        Planner(_cfg()).plan(np.zeros((4, 4)))


def test_graph_ingestion_from_dense():
    a = np.zeros((5, 4))
    a[[0, 0, 1, 1, 2], [0, 1, 0, 1, 3]] = 1
    g = BipartiteGraph.from_dense(a)
    assert (g.n_u, g.n_v, g.m) == (5, 4, 5)
    np.testing.assert_array_equal(
        BipartiteGraph.from_dense(a.astype(bool)).edges_u, g.edges_u)
    with pytest.raises(ValueError, match="2-D"):
        BipartiteGraph.from_dense(np.zeros(3))
    with pytest.raises(ValueError, match="0/1"):
        BipartiteGraph.from_dense(np.full((2, 2), 2.0))


# --------------------------------------------------------------------- #
# Executor: decompose + the cross-graph executable cache
# --------------------------------------------------------------------- #
def test_executor_decompose_matches_oracle_and_compat():
    g = GRAPH_CASES["powerlaw"]()
    tb, _ = bup_oracle(g)
    ex = Executor(_cfg())
    td = ex.decompose(g)
    np.testing.assert_array_equal(td.theta, tb)
    t_legacy, _ = tip_decompose(
        g, ReceiptConfig(num_partitions=6, kernel_blocks=SMALL_BLOCKS,
                         backend="xla"))
    np.testing.assert_array_equal(td.theta, t_legacy)
    assert td.stats.num_subsets >= 1
    assert td.plan.measured.runs == 1


def test_executor_cache_hits_and_misses():
    ex = Executor(_cfg())
    g1 = powerlaw_bipartite(100, 60, 700, seed=0)
    ex.decompose(g1)
    assert ex.cache_stats == dict(entries=1, hits=0, misses=1,
                                  quarantined=0, fallback_runs=0)
    ex.decompose(powerlaw_bipartite(100, 60, 700, seed=5))
    assert ex.cache_stats["hits"] == 1
    ex.decompose(powerlaw_bipartite(420, 60, 700, seed=0))  # new bucket
    assert ex.cache_stats["entries"] == 2
    assert ex.cache_stats["misses"] == 2


@pytest.mark.parametrize("dispatch,dgm", [("subset", False),
                                          ("graph", True)])
def test_executor_cache_skips_tracing_on_same_signature(dispatch, dgm):
    """The acceptance claim: decomposing K graphs of the same bucketed
    shape traces the pipeline EXACTLY once.  Isomorphic copies share
    every support/wedge multiset, so with the cache pinning the measured
    peel widths and stack shapes, runs 2..K are pure jit-cache hits —
    the jax tracing counter must stay at zero.  (Host re-induction
    re-buckets data-dependently, so the subset-dispatch case runs with
    use_dgm=False; the graph dispatch compacts on device at fixed
    shapes and keeps DGM on.)"""
    from jax._src import test_util as jtu

    base = powerlaw_bipartite(90, 50, 600, seed=2)
    graphs = [base] + [_permuted_copy(base, s) for s in (1, 2, 3)]
    ex = Executor(_cfg(cd_dispatch=dispatch, use_dgm=dgm,
                       num_partitions=4))
    tb, _ = bup_oracle(base)
    cold = ex.decompose(graphs[0])                 # traces everything
    np.testing.assert_array_equal(cold.theta, tb)
    for g in graphs[1:]:
        with jtu.count_jit_tracing_cache_miss() as misses:
            td = ex.decompose(g)
        assert misses() == 0, (
            f"same-signature decompose retraced {misses()} function(s)")
        # cached executions stay bit-identical to a cold run
        cold_ref = Executor(_cfg(cd_dispatch=dispatch, use_dgm=dgm,
                                 num_partitions=4)).decompose(g)
        np.testing.assert_array_equal(td.theta, cold_ref.theta)


def test_executor_cache_different_signature_retraces():
    """A different bucketed shape MUST miss (and trace)."""
    from jax._src import test_util as jtu

    ex = Executor(_cfg(num_partitions=4))
    ex.decompose(powerlaw_bipartite(90, 50, 600, seed=2))
    with jtu.count_jit_tracing_cache_miss() as misses:
        ex.decompose(powerlaw_bipartite(400, 220, 2400, seed=2))
    assert misses() > 0
    assert ex.cache_stats["entries"] == 2


def test_executor_cache_hit_skips_graph_dispatch_sizing_sync():
    """On a cache hit the graph dispatch reuses the measured peel width
    instead of sizing from a host snapshot: the whole CD phase drops to
    ONE blocking round trip."""
    base = powerlaw_bipartite(90, 50, 600, seed=2)
    ex = Executor(_cfg(cd_dispatch="graph", num_partitions=4))
    first = ex.decompose(base)
    second = ex.decompose(_permuted_copy(base, 7))
    assert first.stats.overflow_fallbacks == 0
    assert second.stats.host_round_trips < first.stats.host_round_trips


# --------------------------------------------------------------------- #
# measured peel widths (the ROADMAP deferred item, PR 5 satellite)
# --------------------------------------------------------------------- #
def test_fd_peel_width_probe_replaces_static_heuristic():
    """FD gather widths are sized from the host support snapshot (level
    multiplicities), not mm/8 — recorded per group in RunStats, with the
    measured max level riding back from the device loop."""
    g = GRAPH_CASES["powerlaw"]()
    td = Executor(_cfg()).decompose(g)
    s = td.stats
    assert s.fd_peel_widths and len(s.fd_peel_widths) == s.fd_groups
    assert len(s.fd_max_levels) == s.fd_groups
    assert all(w >= 8 for w in s.fd_peel_widths)
    # the probe is data-derived: measured levels bound the width choice
    # wherever the mask fallback did not fire
    for w, lvl in zip(s.fd_peel_widths, s.fd_max_levels):
        assert lvl <= w or s.fd_mask_fallbacks > 0


def test_fd_measured_width_feeds_back_through_plan():
    base = powerlaw_bipartite(90, 50, 600, seed=2)
    ex = Executor(_cfg(num_partitions=4, use_dgm=False))
    ex.decompose(base)
    sig = next(iter(ex._entries))
    entry = ex._entries[sig]
    assert entry.cd_peel_width is not None
    assert entry.fd_level_widths, "FD widths must be recorded per shape"
    widths_before = dict(entry.fd_level_widths)
    td2 = ex.decompose(_permuted_copy(base, 11))
    # the second run consumed the recorded widths: every group whose
    # stack shape was seen before reuses the recorded (traced) width
    assert td2.plan.measured.cd_peel_width == entry.cd_peel_width
    for shape, width in widths_before.items():
        assert entry.fd_level_widths[shape] == width


def test_fd_undersized_hint_stays_exact_via_mask_fallback():
    """An absurdly small pinned width forces the on-device mask-form
    fallback — exactness must survive, and the fallback is counted."""
    g = GRAPH_CASES["vhub"]()
    tb, _ = bup_oracle(g)
    td = Executor(_cfg(peel_width=8)).decompose(g)
    np.testing.assert_array_equal(td.theta, tb)


# --------------------------------------------------------------------- #
# Executor.map: multi-graph batched decomposition
# --------------------------------------------------------------------- #
def test_map_bit_identical_to_per_graph_and_fewer_dispatches():
    """The acceptance claim: Executor.map over >= 8 small graphs issues
    FEWER device dispatches than 8 sequential tip_decompose calls while
    producing bit-identical tip numbers."""
    graphs = [powerlaw_bipartite(60, 40, 350, seed=s) for s in range(8)]
    cfg = _cfg(num_partitions=4)
    ex = Executor(cfg)
    tds = ex.map(graphs)
    assert len(tds) == 8
    seq_dispatches = 0
    rcfg = cfg.to_receipt_config()
    for g, td in zip(graphs, tds):
        t_seq, s_seq = tip_decompose(g, rcfg)
        np.testing.assert_array_equal(td.theta, t_seq)
        tb, _ = bup_oracle(g)
        np.testing.assert_array_equal(td.theta, tb)
        seq_dispatches += s_seq.device_loop_calls + s_seq.host_round_trips
    rep = ex.last_map_report
    map_dispatches = (rep["device_loop_calls"] + rep["counting_dispatches"]
                      + rep["host_round_trips"])
    assert map_dispatches < seq_dispatches, (map_dispatches, seq_dispatches)
    assert rep["n_graphs"] == 8 and rep["chunks"] >= 1


def test_map_mixed_shapes_and_sides():
    """Graphs of different buckets group separately; side='V' peels the
    other vertex set per graph."""
    gs = [powerlaw_bipartite(40, 30, 200, seed=s) for s in range(3)]
    gs += [powerlaw_bipartite(150, 80, 900, seed=s) for s in range(2)]
    ex = Executor(_cfg(num_partitions=4))
    tds = ex.map(gs)
    assert ex.last_map_report["groups"] >= 2
    for g, td in zip(gs, tds):
        tb, _ = bup_oracle(g)
        np.testing.assert_array_equal(td.theta, tb)

    exv = Executor(_cfg(side="V"))
    tdv = exv.map(gs[:2])
    for g, td in zip(gs[:2], tdv):
        tbv, _ = bup_oracle(g.transposed())
        np.testing.assert_array_equal(td.theta, tbv)


def test_map_reuses_executables_across_calls():
    """A second fleet of the same bucketed shape runs out of the cache
    (hit-rate reported, no retracing)."""
    from jax._src import test_util as jtu

    mk = lambda seed: [powerlaw_bipartite(60, 40, 350, seed=s)
                       for s in range(seed, seed + 6)]
    ex = Executor(_cfg())
    ex.map(mk(0))
    assert ex.last_map_report["cache_misses"] >= 1
    with jtu.count_jit_tracing_cache_miss() as misses:
        tds = ex.map(mk(20))
    assert misses() == 0, "same-shape fleet must not retrace"
    assert ex.last_map_report["cache_hits"] >= 1
    for g, td in zip(mk(20), tds):
        tb, _ = bup_oracle(g)
        np.testing.assert_array_equal(td.theta, tb)


def test_map_edge_cases():
    assert Executor(_cfg()).map([]) == []
    # an edgeless graph has all-zero tips; a tiny dense one is fine too
    g0 = BipartiteGraph.from_edges(5, 4, [], [])
    g1 = GRAPH_CASES["fig1"]()
    ex = Executor(_cfg(num_partitions=2))
    tds = ex.map([g0, g1])
    np.testing.assert_array_equal(tds[0].theta, np.zeros(5, np.int64))
    tb, _ = bup_oracle(g1)
    np.testing.assert_array_equal(tds[1].theta, tb)


def test_map_respects_stack_cell_budget():
    """Oversized fleets split into LPT-balanced chunks."""
    graphs = [powerlaw_bipartite(60, 40, 350, seed=s) for s in range(9)]
    ex = Executor(_cfg(), map_stack_cells=64 * 64 * 2)   # ~2 graphs/chunk
    tds = ex.map(graphs)
    assert ex.last_map_report["chunks"] >= 4
    for g, td in zip(graphs, tds):
        tb, _ = bup_oracle(g)
        np.testing.assert_array_equal(td.theta, tb)


# --------------------------------------------------------------------- #
# TipDecomposition: hierarchy queries
# --------------------------------------------------------------------- #
def test_tip_decomposition_queries():
    g = GRAPH_CASES["fig1"]()
    td = decompose(g, _cfg(num_partitions=2))
    tb, _ = bup_oracle(g)                          # [2, 3, 3, 1]
    np.testing.assert_array_equal(td.theta, tb)
    assert td.n == 4
    assert td.vertex_tip(1) == 3
    assert td.max_theta() == 3
    with pytest.raises(IndexError):
        td.vertex_tip(99)
    sub, members, v_ids = td.subgraph_at(3)
    np.testing.assert_array_equal(members, [1, 2])   # the 3-tip: u2, u3
    assert sub.n_u == 2 and sub.m > 0
    sub_all, members_all, _ = td.subgraph_at(0)
    assert members_all.size == g.n_u


def test_decompose_convenience_accepts_all_config_currencies():
    g = GRAPH_CASES["fig1"]()
    tb, _ = bup_oracle(g)
    for cfg in (None, _cfg(num_partitions=2),
                ReceiptConfig(num_partitions=2, kernel_blocks=SMALL_BLOCKS,
                              backend="xla")):
        td = decompose(g, cfg)
        np.testing.assert_array_equal(td.theta, tb)
    with pytest.raises(ValueError, match="EngineConfig or ReceiptConfig"):
        decompose(g, {"num_partitions": 2})
