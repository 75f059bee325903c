"""Serving-layer tests (DESIGN.md §11): ingestion/query/versioning,
admission batching through ``Executor.map``, the incremental-refresh
differential suite (bit-identical to from-scratch on every mutation
step, with dirty-subset evidence in RunStats), concurrent serving, and
the service error taxonomy."""
import dataclasses
import threading

import numpy as np
import pytest

from repro.api import (
    DatasetNotFoundError,
    Decomposition,
    EngineConfig,
    Executor,
    GraphValidationError,
    PlanInfeasibleError,
    ServiceUnavailableError,
    StaleReadError,
)
from repro.core.graph import BipartiteGraph, random_bipartite
from repro.data.synthetic import interaction_graph
from repro.service import (
    DecompositionService,
    RequestQueue,
    ServiceConfig,
    WorkItem,
)

SMALL_BLOCKS = (8, 8, 8)


def _cfg(**kw):
    base = dict(num_partitions=6, kernel_blocks=SMALL_BLOCKS,
                backend="xla", degree_sort=False)
    base.update(kw)
    return EngineConfig(**base)


def _svc(service=None, **kw):
    return DecompositionService(_cfg(**kw), service)


def _keys(g):
    return g.edges_u.astype(np.int64) * g.n_v + g.edges_v.astype(np.int64)


def _fresh_edges(g, count, rng, u_pool=None, v_pool=None):
    have = set(_keys(g).tolist())
    out = []
    pool = np.arange(g.n_u) if u_pool is None else np.asarray(u_pool)
    vpool = np.arange(g.n_v) if v_pool is None else np.asarray(v_pool)
    while len(out) < count:
        u = int(rng.choice(pool))
        v = int(rng.choice(vpool))
        if u * g.n_v + v not in have:
            have.add(u * g.n_v + v)
            out.append((u, v))
    return np.array(out, np.int64).reshape(-1, 2)


# --------------------------------------------------------------------- #
# ingestion / query / versioning
# --------------------------------------------------------------------- #
def test_ingest_query_matches_direct_decompose():
    g = interaction_graph(60, 40, 400, seed=1)
    svc = _svc()
    assert svc.ingest("d", g) == 1
    dec = svc.query("d")
    assert isinstance(dec, Decomposition)
    ref = Executor(_cfg()).decompose(g)
    np.testing.assert_array_equal(dec.numbers, ref.numbers)
    assert svc.max_level("d") == ref.max_level()
    assert svc.tip_number("d", 3) == int(ref.numbers[3])
    sub, members, _ = svc.subgraph_at("d", 2)
    rsub, rmem, _ = ref.subgraph_at(2)
    np.testing.assert_array_equal(members, rmem)
    np.testing.assert_array_equal(_keys(sub), _keys(rsub))


def test_ingest_forms_and_validation():
    svc = _svc()
    svc.ingest("from-edges", edges=([0, 0, 1, 1], [0, 1, 0, 1]),
               n_u=3, n_v=3)
    assert svc.max_level("from-edges") == 1
    a = np.zeros((3, 3))
    a[[0, 0, 1, 1], [0, 1, 0, 1]] = 1
    svc.ingest("from-dense", a)
    np.testing.assert_array_equal(svc.query("from-dense").numbers,
                                  svc.query("from-edges").numbers)
    with pytest.raises(GraphValidationError):
        svc.ingest("bad", edges=([0], [99]), n_u=3, n_v=3)
    with pytest.raises(GraphValidationError):
        svc.ingest("from-dense", a)            # exists, replace not set
    assert svc.ingest("from-dense", a, replace=True) == 2


def test_version_monotonicity_and_mutation_validation():
    g = random_bipartite(30, 20, 0.2, seed=2)
    svc = _svc()
    v = svc.ingest("d", g)
    seen = [v]
    rng = np.random.default_rng(0)
    ins = _fresh_edges(g, 3, rng)
    seen.append(svc.insert_edges("d", ins[:, 0], ins[:, 1]))
    seen.append(svc.delete_edges("d", [g.edges_u[0]], [g.edges_v[0]]))
    assert seen == sorted(seen) and len(set(seen)) == len(seen)
    # inserting a present edge / deleting a missing edge fail validated
    with pytest.raises(GraphValidationError):
        svc.insert_edges("d", ins[:1, 0], ins[:1, 1])
    with pytest.raises(GraphValidationError):
        svc.delete_edges("d", [g.edges_u[0]], [g.edges_v[0]])
    # failed mutations must not bump the version
    assert svc.report()["datasets"]["d"]["version"] == seen[-1]


def test_wing_dataset_served_through_same_interface():
    g = random_bipartite(25, 20, 0.25, seed=3)
    svc = _svc()
    svc.ingest("w", g, workload="wing")
    dec = svc.query("w")
    ref = Executor(_cfg(workload="wing")).decompose(g)
    np.testing.assert_array_equal(dec.numbers, ref.numbers)
    assert svc.psi("w", 0) == int(ref.numbers[0])
    with pytest.raises(ServiceUnavailableError):
        svc.tip_number("w", 0)                 # wrong-workload query


# --------------------------------------------------------------------- #
# admission batching
# --------------------------------------------------------------------- #
def test_flush_batches_compatible_fulls_through_map():
    svc = _svc()
    graphs = [interaction_graph(48, 32, 300, seed=s) for s in range(3)]
    for i, g in enumerate(graphs):
        svc.ingest(f"d{i}", g)
    rep = svc.flush()
    assert rep["fleets"] == 1 and rep["mapped"] == 3
    ex = Executor(_cfg())
    for i, g in enumerate(graphs):
        np.testing.assert_array_equal(svc.query(f"d{i}").numbers,
                                      ex.decompose(g).numbers)
    # fleet below map_min_fleet runs per-graph (no map fleet)
    svc.ingest("solo", interaction_graph(48, 32, 300, seed=9))
    rep = svc.flush()
    assert rep["fleets"] == 0 and rep["full"] == 1


def test_warm_repeat_queries_hit_cache_without_new_dispatches():
    svc = _svc()
    g = interaction_graph(48, 32, 300, seed=4)
    svc.ingest("d", g)
    svc.query("d")                              # computes
    before = svc.report()
    for _ in range(5):
        svc.query("d")
    after = svc.report()
    ds_b, ds_a = before["datasets"]["d"], after["datasets"]["d"]
    assert ds_a["query_hits"] - ds_b["query_hits"] == 5
    # no further engine work ran: executor cache state unchanged
    assert after["executors"]["tip"] == before["executors"]["tip"]


def test_queue_coalesces_and_admission_controls():
    q = RequestQueue(max_pending=2)
    q.submit(WorkItem("a", "refresh", 1))
    q.submit(WorkItem("a", "full", 2))          # upgrades in place
    q.submit(WorkItem("a", "refresh", 3))       # full never degrades
    assert len(q) == 1
    item = q.drain("a")[0]
    assert item.kind == "full" and item.version == 3
    q.submit(WorkItem("a", "refresh", 1))
    q.submit(WorkItem("b", "refresh", 1))
    with pytest.raises(ServiceUnavailableError):
        q.submit(WorkItem("c", "refresh", 1))
    assert q.rejected == 1
    with pytest.raises(ValueError):
        WorkItem("a", "florp", 1)


# --------------------------------------------------------------------- #
# incremental refresh: differential suite
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("workload", ["tip", "wing"])
def test_refresh_differential_random_sequences(workload):
    """Random insert/delete sequences: the refreshed numbers must be
    bit-identical to from-scratch decomposition on EVERY step, and at
    least one step must re-peel only a strict subset of the stored CD
    subsets (the dirty-subset invariant, asserted via RunStats)."""
    rng = np.random.default_rng(11)
    if workload == "tip":
        g = interaction_graph(72, 48, 560, seed=7)
    else:
        # needs enough psi spread for a multi-subset CD ladder — a flat
        # ER graph collapses to one range and nothing can be partial
        g = interaction_graph(48, 40, 360, seed=7)
    svc = _svc(ServiceConfig(refresh_dirty_threshold=0.2),
               num_partitions=8 if workload == "tip" else 6)
    ref_ex = Executor(_cfg(workload=workload,
                           num_partitions=8 if workload == "tip" else 6))
    svc.ingest("d", g, workload=workload)
    svc.query("d")
    partial_steps = 0
    delta_steps = 0
    for step in range(6):
        cur = svc._datasets["d"].graph
        # bias mutations onto low-degree endpoints (both sides) so the
        # mutation ceiling stays below the top CD bounds on some steps
        du, dv = cur.degrees_u(), cur.degrees_v()
        pool = np.argsort(du)[: max(8, cur.n_u // 3)]
        vpool = np.argsort(dv)[: max(8, cur.n_v // 3)]
        ins = _fresh_edges(cur, 3, rng, u_pool=pool, v_pool=vpool)
        svc.insert_edges("d", ins[:, 0], ins[:, 1])
        low = np.argsort(du[cur.edges_u] + dv[cur.edges_v],
                         kind="stable")[:3]
        svc.delete_edges("d", cur.edges_u[low], cur.edges_v[low])
        dec = svc.query("d")
        ref = ref_ex.decompose(svc._datasets["d"].graph)
        np.testing.assert_array_equal(
            np.asarray(dec.numbers), np.asarray(ref.numbers),
            err_msg=f"step {step} refresh diverged from from-scratch")
        s = dec.stats
        if s.refresh_mode == "delta":
            delta_steps += 1
            assert s.refresh_stop > s.refresh_t_hi
            if s.refresh_subsets_repeeled < s.refresh_subsets_total:
                partial_steps += 1
    assert delta_steps >= 4, "dirty threshold unexpectedly forced fulls"
    assert partial_steps >= 1, (
        "no step re-peeled a strict subset — dirty-subset containment "
        "never exercised")


@pytest.mark.parametrize("backend", ["interpret", "interpret_sparse"])
def test_refresh_gathered_levels_exact_on_kernel_backends(backend):
    """The refresh prefix loop gathers a small peel level into its
    ``peel_width`` buffer and takes the mask form for a larger one; both
    forms, through the kernel bodies, stay bit-identical to
    from-scratch."""
    rng = np.random.default_rng(5)
    g = interaction_graph(64, 40, 420, seed=3)
    svc = _svc(ServiceConfig(refresh_dirty_threshold=0.2), backend=backend)
    ref_ex = Executor(_cfg(backend=backend))
    svc.ingest("d", g)
    svc.query("d")
    for step in range(2):
        cur = svc._datasets["d"].graph
        ins = _fresh_edges(cur, 3, rng)
        svc.insert_edges("d", ins[:, 0], ins[:, 1])
        drop = rng.choice(cur.m, 3, replace=False)
        svc.delete_edges("d", cur.edges_u[drop], cur.edges_v[drop])
        dec = svc.query("d")
        assert dec.stats.refresh_mode == "delta"
        ref = ref_ex.decompose(svc._datasets["d"].graph)
        np.testing.assert_array_equal(
            np.asarray(dec.numbers), np.asarray(ref.numbers),
            err_msg=f"step {step}: {backend} refresh diverged")


def test_refresh_falls_back_to_full_past_dirty_threshold():
    g = interaction_graph(60, 40, 420, seed=8)
    svc = _svc(ServiceConfig(refresh_dirty_threshold=0.01))
    svc.ingest("d", g)
    svc.query("d")
    rng = np.random.default_rng(2)
    ins = _fresh_edges(g, 30, rng)               # ~7% dirty > 1%
    svc.insert_edges("d", ins[:, 0], ins[:, 1])
    dec = svc.query("d")
    assert dec.stats.refresh_mode == "full"
    assert svc.report()["datasets"]["d"]["full_recomputes"] >= 1
    ref = Executor(_cfg()).decompose(svc._datasets["d"].graph)
    np.testing.assert_array_equal(dec.numbers, ref.numbers)


def test_refresh_net_noop_serves_without_recompute():
    g = random_bipartite(30, 20, 0.2, seed=9)
    svc = _svc()
    svc.ingest("d", g)
    first = svc.query("d")
    rng = np.random.default_rng(3)
    ins = _fresh_edges(g, 2, rng)
    svc.insert_edges("d", ins[:, 0], ins[:, 1])
    svc.delete_edges("d", ins[:, 0], ins[:, 1])   # net no-op
    dec = svc.query("d")
    assert dec is first                           # same object: no rerun
    rep = svc.report()["datasets"]["d"]
    assert rep["refreshes"] == 0 and rep["fresh"]


# --------------------------------------------------------------------- #
# staleness policies
# --------------------------------------------------------------------- #
def test_staleness_strict_raises_and_flush_clears():
    g = random_bipartite(30, 20, 0.2, seed=10)
    svc = _svc(ServiceConfig(staleness="strict"))
    svc.ingest("d", g)
    with pytest.raises(StaleReadError):           # never computed yet
        svc.query("d")
    svc.flush()
    svc.query("d")
    svc.delete_edges("d", [g.edges_u[0]], [g.edges_v[0]])
    with pytest.raises(StaleReadError) as ei:
        svc.query("d")
    assert ei.value.context["version"] > ei.value.context["result_version"]
    svc.flush()
    assert svc.query("d") is not None


def test_staleness_stale_ok_serves_old_result():
    g = random_bipartite(30, 20, 0.2, seed=12)
    svc = _svc(ServiceConfig(staleness="stale_ok"))
    svc.ingest("d", g)
    svc.flush()
    first = svc.query("d")
    svc.delete_edges("d", [g.edges_u[0]], [g.edges_v[0]])
    assert svc.query("d") is first                # stale but served
    assert svc.report()["datasets"]["d"]["stale_reads"] == 1
    svc.flush()
    assert svc.query("d") is not first


# --------------------------------------------------------------------- #
# error taxonomy
# --------------------------------------------------------------------- #
def test_unknown_dataset_raises_structured_keyerror():
    svc = _svc()
    with pytest.raises(DatasetNotFoundError) as ei:
        svc.query("nope")
    assert isinstance(ei.value, KeyError)
    assert ei.value.context["dataset"] == "nope"
    with pytest.raises(DatasetNotFoundError):
        svc.drop("nope")


def test_map_wing_rejection_is_plan_infeasible():
    ex = Executor(_cfg(workload="wing"))
    g = random_bipartite(10, 8, 0.3, seed=1)
    with pytest.raises(PlanInfeasibleError):
        ex.map([g])
    with pytest.raises(ValueError):               # taxonomy compat
        ex.map([g])


def test_service_config_validation():
    with pytest.raises(ValueError):
        ServiceConfig(refresh_dirty_threshold=1.5)
    with pytest.raises(ValueError):
        ServiceConfig(staleness="eventual")
    with pytest.raises(ValueError):
        ServiceConfig(map_min_fleet=1)


# --------------------------------------------------------------------- #
# protocol + describe
# --------------------------------------------------------------------- #
def test_decomposition_protocol_and_aliases():
    g = random_bipartite(25, 20, 0.25, seed=13)
    tip = Executor(_cfg()).decompose(g)
    wing = Executor(_cfg(workload="wing")).decompose(g)
    for dec in (tip, wing):
        assert isinstance(dec, Decomposition)
        assert dec.max_level() == (int(dec.numbers.max())
                                   if dec.numbers.size else 0)
        d = dec.to_dict()
        assert d["numbers"] == [int(x) for x in dec.numbers]
        assert d["max_level"] == dec.max_level()
    # deprecated aliases stay bit-compatible
    assert tip.max_theta() == tip.max_level()
    assert wing.max_psi() == wing.max_level()
    assert tip.vertex_tip(0) == int(tip.numbers[0])
    assert wing.edge_psi(0) == int(wing.numbers[0])
    assert tip.to_dict()["workload"] == "tip"
    assert wing.to_dict()["axis"] == "edge"


def test_engine_config_describe_renders_resolved_knobs():
    text = _cfg(num_partitions=4).describe()
    assert "backend:" in text and "'xla'" in text
    assert "num_partitions" in text and "[non-default]" in text
    svc = _svc()
    desc = svc.describe()
    assert "ServiceConfig" in desc and "staleness" in desc


# --------------------------------------------------------------------- #
# concurrent serving
# --------------------------------------------------------------------- #
def test_concurrent_interleaved_ingest_query_refresh():
    """Two datasets, four threads interleaving mutations and queries:
    every answer must match a from-scratch decomposition of the graph
    version it was served at, versions stay monotone, and the warm
    query path keeps hitting the cache."""
    rng = np.random.default_rng(21)
    svc = _svc(ServiceConfig(refresh_dirty_threshold=0.5))
    gs = {"x": interaction_graph(56, 36, 380, seed=31),
          "y": interaction_graph(56, 36, 380, seed=32)}
    for name, g in gs.items():
        svc.ingest(name, g)
    svc.flush()                                   # one map fleet warm-up
    errors = []
    versions = {"x": [], "y": []}
    answers = []                                  # (name, keys, numbers)

    def mutator(name, seed):
        r = np.random.default_rng(seed)
        try:
            for _ in range(3):
                with svc._lock:                   # mutations atomic in pairs
                    cur = svc._datasets[name].graph
                    ins = _fresh_edges(cur, 2, r)
                    v1 = svc.insert_edges(name, ins[:, 0], ins[:, 1])
                    cur = svc._datasets[name].graph
                    drop = r.choice(cur.m, 2, replace=False)
                    v2 = svc.delete_edges(name, cur.edges_u[drop],
                                          cur.edges_v[drop])
                versions[name] += [v1, v2]
                svc.query(name)
        except Exception as exc:                  # surfaced after join
            errors.append(exc)

    def reader(name):
        try:
            for _ in range(6):
                with svc._lock:                   # snapshot version+answer
                    dec = svc.query(name)
                    gsnap = svc._datasets[name].base_graph
                answers.append((name, _keys(gsnap),
                                np.asarray(dec.numbers).copy()))
        except Exception as exc:
            errors.append(exc)

    threads = [threading.Thread(target=mutator, args=("x", 1)),
               threading.Thread(target=mutator, args=("y", 2)),
               threading.Thread(target=reader, args=("x",)),
               threading.Thread(target=reader, args=("y",))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errors, errors
    for name in ("x", "y"):
        assert versions[name] == sorted(versions[name])
        assert len(set(versions[name])) == len(versions[name])
    # every served answer is bit-identical to from-scratch on the graph
    # it was served against
    ex = Executor(_cfg())
    checked = set()
    for name, keys, numbers in answers:
        sig = (name, keys.tobytes())
        if sig in checked:
            continue
        checked.add(sig)
        g = gs[name]
        gg = BipartiteGraph.from_edges(g.n_u, g.n_v,
                                       keys // g.n_v, keys % g.n_v)
        np.testing.assert_array_equal(numbers, ex.decompose(gg).numbers)
    rep = svc.report()
    # warm expectation: most queries after the initial computes are hits
    total_q = sum(d["queries"] for d in rep["datasets"].values())
    hits = sum(d["query_hits"] for d in rep["datasets"].values())
    assert hits >= total_q // 3
    assert rep["queue"]["pending"] == 0
