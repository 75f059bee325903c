"""Hardened decompose runtime (PR 6 tentpole): the structured error
taxonomy, the deterministic fault-injection harness, graceful
degradation (backend fallback chain, quarantine, admission control,
bounded overflow replay), fleet isolation in ``Executor.map``, verify
mode, and the degenerate-graph battery."""
import numpy as np
import pytest

from repro.api import (
    EngineConfig,
    Executor,
    decompose,
    verify_tip_decomposition,
)
from repro.api.errors import (
    FleetPartialFailure,
    GraphValidationError,
    KernelBackendError,
    PlanInfeasibleError,
    ReceiptError,
    VerificationError,
)
from repro.api.faults import FaultInjector, FaultSpec, fault_point, inject
from repro.core.graph import BipartiteGraph, random_bipartite
from repro.core.peeling import bup_oracle

from conftest import GRAPH_CASES

SMALL_BLOCKS = (8, 8, 8)


def _cfg(**kw):
    base = dict(num_partitions=3, kernel_blocks=SMALL_BLOCKS, backend="xla")
    base.update(kw)
    return EngineConfig(**base)


def _er(nu, nv, ne, seed):
    rng = np.random.default_rng(seed)
    return BipartiteGraph.from_edges(
        nu, nv, rng.integers(0, nu, ne), rng.integers(0, nv, ne))


# --------------------------------------------------------------------- #
# satellite 1: from_dense / validate ingestion battery
# --------------------------------------------------------------------- #
class TestGraphValidation:
    def test_from_dense_rejects_nan_and_inf(self):
        a = np.ones((4, 3))
        a[1, 2] = np.nan
        with pytest.raises(GraphValidationError, match="non-finite"):
            BipartiteGraph.from_dense(a)
        a[1, 2] = np.inf
        with pytest.raises(GraphValidationError, match="non-finite"):
            BipartiteGraph.from_dense(a)
        # binarize is NOT an escape hatch for non-finite input
        with pytest.raises(GraphValidationError, match="binarize"):
            BipartiteGraph.from_dense(a, binarize=True)

    def test_from_dense_rejects_negative_and_weighted(self):
        a = np.zeros((4, 3))
        a[0, 0] = -1.0
        with pytest.raises(GraphValidationError, match="0/1"):
            BipartiteGraph.from_dense(a)
        a[0, 0] = 2.5
        with pytest.raises(GraphValidationError, match="binarize"):
            BipartiteGraph.from_dense(a)

    def test_from_dense_binarize_escape_hatch(self):
        a = np.zeros((4, 3))
        a[0, 0] = 2.5
        a[2, 1] = 7.0
        g = BipartiteGraph.from_dense(a, binarize=True)
        assert g.edges_u.size == 2
        assert sorted(zip(g.edges_u.tolist(), g.edges_v.tolist())) == \
            [(0, 0), (2, 1)]

    def test_from_dense_rejects_zero_size_and_wrong_rank(self):
        with pytest.raises(GraphValidationError, match="zero-size"):
            BipartiteGraph.from_dense(np.zeros((0, 5)))
        with pytest.raises(GraphValidationError, match="2-D"):
            BipartiteGraph.from_dense(np.zeros((2, 2, 2)))

    def test_validation_errors_are_valueerrors(self):
        # pre-hardening handlers caught ValueError; keep them working
        with pytest.raises(ValueError):
            BipartiteGraph.from_dense(np.full((2, 2), np.nan))
        with pytest.raises(ValueError):
            BipartiteGraph.from_edges(2, 2, [5], [0])

    def test_validate_catches_internal_corruption(self):
        g = BipartiteGraph(4, 4, np.array([9]), np.array([0]))
        with pytest.raises(GraphValidationError, match="out of range"):
            g.validate()
        g2 = BipartiteGraph(4, 4, np.array([1, 2]), np.array([0]))
        with pytest.raises(GraphValidationError, match="parallel"):
            g2.validate()
        ok = GRAPH_CASES["fig1"]()
        assert ok.validate() is ok


# --------------------------------------------------------------------- #
# degenerate graphs x dispatch x backend (satellite 3a)
# --------------------------------------------------------------------- #
DEGENERATE = {
    "empty_edges": GRAPH_CASES["empty_edges"],
    "star": GRAPH_CASES["star"],                     # butterfly-free
    "single_vertex_side": lambda: BipartiteGraph.from_edges(
        1, 5, [0] * 5, list(range(5))),
    "all_ones_dense": lambda: BipartiteGraph.from_dense(
        np.ones((8, 6))),
}


@pytest.mark.parametrize("name", sorted(DEGENERATE))
@pytest.mark.parametrize("dispatch", ["subset", "graph"])
@pytest.mark.parametrize("backend", ["xla", "interpret",
                                     "interpret_sparse"])
def test_degenerate_graphs_every_mode(name, dispatch, backend):
    g = DEGENERATE[name]()
    tb, _ = bup_oracle(g)
    td = Executor(_cfg(cd_dispatch=dispatch, backend=backend)).decompose(
        g, verify=True)
    np.testing.assert_array_equal(td.theta, tb)
    assert td.stats.verified and td.stats.verify_checks >= 1


# --------------------------------------------------------------------- #
# fault grammar (tentpole b)
# --------------------------------------------------------------------- #
class TestFaultSpec:
    def test_parse_full_grammar(self):
        spec = FaultSpec.parse(
            "kernel_launch:backend=interpret@2x3, peel_buffer@1, "
            "map_chunk, dgm_boundary@4x*")
        assert len(spec.rules) == 4
        r = spec.rules[0]
        assert (r.site, r.filters, r.nth, r.count) == (
            "kernel_launch", (("backend", "interpret"),), 2, 3)
        assert spec.rules[1].count == 1
        assert spec.rules[2].nth == 0          # bare site: every hit
        assert spec.rules[3].count == -1       # x*: unbounded

    def test_parse_rejects_unknown_site_with_hint(self):
        with pytest.raises(ValueError, match="kernel_launch"):
            FaultSpec.parse("kernel_lunch@1")
        with pytest.raises(ValueError, match="unknown fault-injection site"):
            FaultSpec.parse("bogus")
        with pytest.raises(ValueError):
            FaultSpec.parse("kernel_launch@0")        # 1-based

    def test_trigger_counting_and_filters(self):
        inj = FaultInjector("kernel_launch:backend=interpret@2")
        with inject(inj):
            assert not fault_point("kernel_launch", backend="xla")
            assert not fault_point("kernel_launch", backend="interpret")
            assert fault_point("kernel_launch", backend="interpret")
            assert not fault_point("kernel_launch", backend="interpret")
        assert inj.report() == [{
            "rule": "kernel_launch:backend=interpret@2",
            "hits": 3, "fired": 1}]

    def test_fault_point_raises_given_error_class(self):
        with inject(FaultInjector("map_chunk@1")):
            with pytest.raises(KernelBackendError) as ei:
                fault_point("map_chunk", KernelBackendError, chunk=0)
        assert ei.value.injected
        assert ei.value.context["site"] == "map_chunk"

    def test_engine_config_validates_fault_spec(self):
        with pytest.raises(ValueError, match="unknown fault-injection site"):
            _cfg(fault_spec="nope@1")


# --------------------------------------------------------------------- #
# graceful degradation (tentpole c)
# --------------------------------------------------------------------- #
class TestDegradation:
    def test_kernel_fault_falls_back_exactly(self):
        g = _er(40, 30, 200, 1)
        base = Executor(_cfg(backend="interpret")).decompose(g).theta
        ex = Executor(_cfg(backend="interpret",
                           fault_spec="kernel_launch:backend=interpret@1"))
        with pytest.warns(RuntimeWarning, match="degrading to 'xla'"):
            td = ex.decompose(g)
        np.testing.assert_array_equal(td.theta, base)
        assert td.stats.backend_used == "xla"
        assert td.stats.backend_fallbacks == ["interpret"]
        assert ex.cache_stats["fallback_runs"] == 1
        assert ex.fault_report[0]["fired"] == 1

    @pytest.mark.parametrize("primary", ["pallas", "pallas_sparse"])
    def test_tpu_chain_skips_interpreter(self, monkeypatch, primary):
        """On a TPU a failed compiled kernel drops straight to the jnp
        oracle: the interpreter is a CPU validation tool."""
        from repro.kernels import ops as kops

        assert kops.fallback_chain(primary)[1].startswith("interpret")
        monkeypatch.setattr(kops.jax, "default_backend", lambda: "tpu")
        assert kops.fallback_chain(primary) == (primary, "xla")
        assert kops.fallback_backend(primary) == "xla"

    def test_repeated_failure_quarantines_signature(self):
        g = _er(40, 30, 200, 1)
        base = Executor(_cfg(backend="interpret")).decompose(g).theta
        ex = Executor(_cfg(backend="interpret",
                           fault_spec="kernel_launch:backend=interpret@1x*"))
        for _ in range(3):
            td = ex.decompose(g)
            np.testing.assert_array_equal(td.theta, base)
        # after _QUARANTINE_AFTER primary failures the signature runs
        # straight on the fallback backend: no more failed launches
        assert ex.cache_stats["quarantined"] == 1
        assert td.stats.quarantined
        assert td.stats.backend_used == "xla"
        assert td.stats.backend_fallbacks == []

    def test_chain_exhaustion_raises_structured(self):
        g = _er(30, 20, 100, 2)
        ex = Executor(_cfg(backend="xla",
                           fault_spec="kernel_launch:backend=xla@1x*"))
        with pytest.raises(KernelBackendError) as ei:
            ex.decompose(g)
        assert ei.value.plan_signature is not None
        assert "xla" in str(ei.value)

    @pytest.mark.parametrize("dispatch", ["subset", "graph"])
    def test_forced_peel_overflow_replay_is_exact(self, dispatch):
        g = _er(40, 30, 200, 1)
        base = Executor(_cfg(cd_dispatch=dispatch)).decompose(g).theta
        td = Executor(_cfg(cd_dispatch=dispatch,
                           fault_spec="peel_buffer@1")).decompose(g)
        np.testing.assert_array_equal(td.theta, base)
        assert td.stats.overflow_fallbacks >= 1

    def test_dgm_boundary_fault_recovers_on_fallback(self):
        g = _er(40, 30, 200, 1)
        base = Executor(_cfg(backend="interpret", cd_dispatch="subset",
                             use_dgm=True)).decompose(g).theta
        td = Executor(_cfg(backend="interpret", cd_dispatch="subset",
                           use_dgm=True,
                           fault_spec="dgm_boundary@1")).decompose(g)
        np.testing.assert_array_equal(td.theta, base)
        assert td.stats.backend_fallbacks == ["interpret"]

    def test_guardrails_off_propagates_and_suppresses(self):
        g = _er(30, 20, 100, 2)
        base = Executor(_cfg()).decompose(g).theta
        # guardrails=False suppresses this executor's own injector too:
        # the bare path must be byte-identical to an uninjected run
        ex = Executor(_cfg(fault_spec="kernel_launch@1x*"),
                      guardrails=False)
        np.testing.assert_array_equal(ex.decompose(g).theta, base)


# --------------------------------------------------------------------- #
# admission control (tentpole c)
# --------------------------------------------------------------------- #
class TestAdmissionControl:
    def test_infeasible_budget_raises(self):
        g = _er(40, 30, 200, 1)
        with pytest.raises(PlanInfeasibleError) as ei:
            Executor(_cfg(memory_budget_bytes=1024)).decompose(g)
        assert "budget" in str(ei.value)
        assert isinstance(ei.value, ValueError)

    def test_moderate_budget_downshifts_partitions(self):
        g = GRAPH_CASES["powerlaw"]()
        ex0 = Executor(_cfg(num_partitions=8))
        plan0 = ex0.plan(g)
        # find a budget that admits the fixed cost but not the 8-way
        # FD stack: walk down until the plan degrades
        budget = plan0.padded_bytes - 1
        ex = Executor(_cfg(num_partitions=8, memory_budget_bytes=budget))
        plan = ex.plan(g)
        assert plan.degraded_from_partitions == 8
        assert plan.num_partitions < 8
        assert plan.padded_bytes <= budget
        # the degraded plan still decomposes exactly
        tb, _ = bup_oracle(g)
        td = ex.decompose(g, plan=plan)
        np.testing.assert_array_equal(td.theta, tb)

    def test_no_budget_means_no_admission_control(self):
        g = GRAPH_CASES["er_small"]()
        plan = Executor(_cfg(num_partitions=4)).plan(g)
        assert plan.memory_budget_bytes is None
        assert plan.degraded_from_partitions is None


# --------------------------------------------------------------------- #
# fleet isolation (tentpole d) — the ISSUE acceptance scenario
# --------------------------------------------------------------------- #
class TestFleetIsolation:
    def _fleet(self):
        return [_er(16, 12, 60, s) for s in range(5)]

    def test_bad_member_isolated_healthy_bit_identical(self):
        fleet = self._fleet()
        clean = Executor(_cfg(fd_mode="level")).map(fleet)
        bad = BipartiteGraph(4, 4, np.array([9]), np.array([0]))
        fleet_bad = fleet[:2] + [bad] + fleet[2:]
        ex = Executor(_cfg(fd_mode="level", fault_spec="map_chunk@1"))
        res = ex.map(fleet_bad)
        assert len(res) == 6
        assert isinstance(res[2], GraphValidationError)
        assert res[2].context["graph_index"] == 2
        healthy = res[:2] + res[3:]
        for got, want in zip(healthy, clean):
            np.testing.assert_array_equal(got.theta, want.theta)
        rep = ex.last_map_report
        assert rep["chunk_failures"] >= 1          # the injected fault
        assert rep["chunk_retries"] + rep["isolated_graphs"] >= 1
        assert list(rep["errors"]) == [2]

    def test_strict_mode_aggregates(self):
        fleet = self._fleet()
        fleet[1] = BipartiteGraph(4, 4, np.array([9]), np.array([0]))
        ex = Executor(_cfg(fd_mode="level"))
        with pytest.raises(FleetPartialFailure) as ei:
            ex.map(fleet, strict=True)
        assert list(ei.value.errors) == [1]
        assert ei.value.n_ok == 4
        assert isinstance(ei.value.errors[1], GraphValidationError)

    def test_non_graph_member_reported_not_raised(self):
        fleet = self._fleet()
        res = Executor(_cfg(fd_mode="level")).map(fleet[:2] + ["nope"])
        assert isinstance(res[2], GraphValidationError)
        assert all(not isinstance(r, ReceiptError) for r in res[:2])

    def test_chunk_fault_retries_on_fallback_backend(self):
        fleet = self._fleet()
        clean = Executor(_cfg(fd_mode="level",
                              backend="interpret")).map(fleet)
        ex = Executor(_cfg(fd_mode="level", backend="interpret",
                           fault_spec="map_chunk:backend=interpret@1"))
        res = ex.map(fleet)
        for got, want in zip(res, clean):
            np.testing.assert_array_equal(got.theta, want.theta)
        rep = ex.last_map_report
        assert rep["chunk_retries"] >= 1
        assert not rep["errors"]
        # the retried chunk ran on the fallback backend
        assert any(r.stats.backend_used == "xla" for r in res)


# --------------------------------------------------------------------- #
# verify mode (tentpole e)
# --------------------------------------------------------------------- #
class TestVerifyMode:
    @pytest.mark.parametrize("name", ["fig1", "er_dense", "powerlaw"])
    def test_verify_passes_on_real_results(self, name):
        g = GRAPH_CASES[name]()
        td = Executor(_cfg(num_partitions=4)).decompose(g, verify=True)
        assert td.stats.verified
        assert td.stats.verify_checks >= 3

    def test_verify_rejects_upward_corruption(self):
        g = GRAPH_CASES["er_dense"]()
        td = Executor(_cfg(num_partitions=4)).decompose(g)
        bad = td.theta.copy()
        bad[0] = bad.max() + 3
        with pytest.raises(VerificationError):
            verify_tip_decomposition(g, "U", bad,
                                     bounds=td.stats.bounds)

    def test_verify_rejects_support_bound_violation(self):
        g = GRAPH_CASES["single_bfly"]()
        with pytest.raises(VerificationError, match="support"):
            verify_tip_decomposition(g, "U", np.array([5, 1]))

    def test_verify_rejects_shape_mismatch(self):
        g = GRAPH_CASES["fig1"]()
        with pytest.raises(VerificationError, match="shape"):
            verify_tip_decomposition(g, "U", np.zeros(3, np.int64))

    def test_verify_map_results_without_bounds(self):
        fleet = [_er(16, 12, 60, s) for s in range(3)]
        res = Executor(_cfg(fd_mode="level")).map(fleet)
        for g, r in zip(fleet, res):
            assert verify_tip_decomposition(g, "U", r.theta) >= 1


# --------------------------------------------------------------------- #
# satellite 2: RestartManager failure log + straggler flagging
# --------------------------------------------------------------------- #
def test_restart_manager_bounded_failure_log(tmp_path):
    from repro.train.checkpoint import CheckpointManager
    from repro.train.fault_tolerance import RestartManager

    rm = RestartManager(CheckpointManager(str(tmp_path)),
                        max_failures=1000, max_failure_log=5)
    for i in range(9):
        rm.record_failure(RuntimeError(f"boom {i}"))
    rep = rm.failure_report()
    assert rm.failures == 9
    assert len(rep) == 5                        # bounded, newest win
    assert [e["message"] for e in rep] == [f"boom {i}" for i in
                                           range(4, 9)]
    assert all(e["type"] == "RuntimeError" and "time" in e for e in rep)


def test_map_straggler_flagging_monkeypatched():
    """Stragglers surface in the report + per-result stats; chunk walls
    are fed to the shared StragglerMonitor (forced here by faking one
    slow chunk EWMA)."""
    fleet = [_er(16, 12, 60, s) for s in range(4)]
    ex = Executor(_cfg(fd_mode="level"), map_stack_cells=16 * 16)
    res = ex.map(fleet)                         # >= 3 chunks recorded
    assert len(ex._stragglers.timings) >= 3
    slow = next(iter(ex._stragglers.timings))
    ex._stragglers.timings[slow].ewma = 1e9     # fake a straggler
    res = ex.map(fleet)
    rep = ex.last_map_report
    assert slow in set(ex._stragglers.stragglers())
    assert rep["stragglers"]
