"""RECEIPT correctness: engine vs the exact BUP oracle (Theorems 1-2)."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.graph import BipartiteGraph, paper_fig1_graph
from repro.core.peeling import bup_oracle, parb_metrics
from repro.core.receipt import ReceiptConfig, tip_decompose

from conftest import GRAPH_CASES

SMALL_BLOCKS = (8, 8, 8)


def _cfg(**kw):
    base = dict(
        num_partitions=6, kernel_blocks=SMALL_BLOCKS, backend="xla"
    )
    base.update(kw)
    return ReceiptConfig(**base)


# --------------------------------------------------------------------- #
# ground truth sanity
# --------------------------------------------------------------------- #
def test_fig1_bup(fig1):
    theta, m = bup_oracle(fig1)
    assert theta.tolist() == [2, 3, 3, 1]
    assert m.rounds == 4


def test_fig1_parb_matches_bup(fig1):
    tb, _ = bup_oracle(fig1)
    tp, mp = parb_metrics(fig1)
    assert (tb == tp).all()
    assert mp.rounds <= 4


# --------------------------------------------------------------------- #
# engine vs oracle across graph shapes and configs
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("case", sorted(GRAPH_CASES))
def test_receipt_matches_bup(case):
    g = GRAPH_CASES[case]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg())
    np.testing.assert_array_equal(tb, tr)
    assert stats.num_subsets >= 1


@pytest.mark.parametrize("p", [1, 2, 4, 16, 64])
def test_receipt_partition_sweep(p):
    g = GRAPH_CASES["powerlaw"]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg(num_partitions=p))
    np.testing.assert_array_equal(tb, tr)
    assert stats.num_subsets <= max(p, 1)


@pytest.mark.parametrize("case", ["vhub", "powerlaw"])
@pytest.mark.parametrize("huc", [True, False])
@pytest.mark.parametrize("dgm", [True, False])
def test_receipt_feature_matrix(case, huc, dgm):
    g = GRAPH_CASES[case]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg(use_huc=huc, use_dgm=dgm))
    np.testing.assert_array_equal(tb, tr)
    if not huc:
        assert stats.huc_recounts == 0
    if not dgm:
        assert stats.dgm_compactions == 0


def test_huc_fires_and_saves_wedges_in_high_r_regime():
    g = GRAPH_CASES["vhub"]()
    _, s_on = tip_decompose(g, _cfg(use_huc=True, num_partitions=12))
    _, s_off = tip_decompose(g, _cfg(use_huc=False, num_partitions=12))
    assert s_on.huc_recounts > 0
    assert s_on.wedges_total < s_off.wedges_total


def test_degree_sort_invariance():
    g = GRAPH_CASES["powerlaw"]()
    tb, _ = bup_oracle(g)
    t1, _ = tip_decompose(g, _cfg(degree_sort=True))
    t2, _ = tip_decompose(g, _cfg(degree_sort=False))
    np.testing.assert_array_equal(tb, t1)
    np.testing.assert_array_equal(tb, t2)


def test_interpret_backend_matches():
    g = GRAPH_CASES["er_small"]()
    tb, _ = bup_oracle(g)
    tr, _ = tip_decompose(g, _cfg(backend="interpret", kernel_blocks=(8, 8, 16)))
    np.testing.assert_array_equal(tb, tr)


def test_sync_reduction_vs_parb():
    """The paper's headline: RECEIPT drastically reduces rho."""
    g = GRAPH_CASES["vhub"]()
    _, mp = parb_metrics(g)
    _, stats = tip_decompose(g, _cfg(num_partitions=8))
    assert stats.rho_cd < mp.rounds


def test_bounds_are_monotone_and_cover():
    g = GRAPH_CASES["powerlaw"]()
    tr, stats = tip_decompose(g, _cfg(num_partitions=8))
    b = stats.bounds
    assert all(b[i] < b[i + 1] for i in range(len(b) - 1))
    assert b[0] == 0.0
    assert tr.max() < b[-1]


def test_subset_ranges_contain_theta():
    """Theorem 1: every vertex's tip number lies in its subset's range."""
    g = GRAPH_CASES["vhub"]()
    cfg = _cfg(num_partitions=8)
    from repro.core.receipt import receipt_cd, RunStats

    stats = RunStats()
    subset_id, init_sup, bounds, _ = receipt_cd(g, cfg, stats)
    tb, _ = bup_oracle(g)
    for u in range(g.n_u):
        i = subset_id[u]
        assert bounds[i] <= tb[u] < bounds[i + 1], (
            f"u={u} theta={tb[u]} not in [{bounds[i]}, {bounds[i+1]})"
        )


def test_init_support_vector():
    """FD init supports equal BUP supports after peeling lower subsets
    (Lemma 1 — order independence)."""
    g = GRAPH_CASES["er_small"]()
    cfg = _cfg(num_partitions=4)
    from repro.core.peeling import shared_butterfly_matrix
    from repro.core.receipt import receipt_cd, RunStats

    stats = RunStats()
    subset_id, init_sup, bounds, _ = receipt_cd(g, cfg, stats)
    b2 = shared_butterfly_matrix(g)
    for i in range(subset_id.max() + 1):
        geq = subset_id >= i
        members = np.where(subset_id == i)[0]
        for u in members:
            expect = b2[u][geq].sum()
            assert init_sup[u] == expect, (u, i, init_sup[u], expect)


# --------------------------------------------------------------------- #
# property-based: random graphs, random configs
# --------------------------------------------------------------------- #
@settings(max_examples=25, deadline=None)
@given(
    n_u=st.integers(2, 40),
    n_v=st.integers(2, 30),
    density=st.floats(0.05, 0.6),
    p=st.integers(1, 10),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_receipt_equals_bup(n_u, n_v, density, p, seed):
    rng = np.random.default_rng(seed)
    a = rng.random((n_u, n_v)) < density
    eu, ev = np.nonzero(a)
    g = BipartiteGraph.from_edges(n_u, n_v, eu, ev)
    tb, _ = bup_oracle(g)
    tr, _ = tip_decompose(g, _cfg(num_partitions=p))
    np.testing.assert_array_equal(tb, tr)


@settings(max_examples=10, deadline=None)
@given(
    n_u=st.integers(4, 30),
    n_hubs=st.integers(1, 4),
    seed=st.integers(0, 1000),
)
def test_property_hub_graphs(n_u, n_hubs, seed):
    """V-hub graphs (the HUC-firing regime) stay exact."""
    rng = np.random.default_rng(seed)
    n_v = n_hubs + 10
    eu, ev = [], []
    for u in range(n_u):
        k = rng.integers(1, n_hubs + 1)
        cols = list(rng.choice(n_hubs, size=k, replace=False))
        cols += list(n_hubs + rng.choice(10, size=2, replace=False))
        eu += [u] * len(cols)
        ev += cols
    g = BipartiteGraph.from_edges(n_u, n_v, eu, ev)
    tb, _ = bup_oracle(g)
    tr, _ = tip_decompose(g, _cfg(num_partitions=4))
    np.testing.assert_array_equal(tb, tr)


# --------------------------------------------------------------------- #
# the device-resident sweep loop
# --------------------------------------------------------------------- #
@pytest.mark.slow
@pytest.mark.parametrize("case", ["er_small", "powerlaw", "vhub", "star",
                                  "empty_edges"])
def test_device_loop_equals_host_loop(case):
    """The fused lax.while_loop engine reproduces the BUP oracle exactly,
    and its subset structure is consistent: every CD sweep is counted in
    exactly one subset and the subset bounds are nondecreasing."""
    g = GRAPH_CASES[case]()
    tb, _ = bup_oracle(g)
    tr, s = tip_decompose(g, _cfg())
    np.testing.assert_array_equal(tb, tr)
    assert sum(s.sweeps_per_subset) == s.rho_cd
    assert len(s.bounds) == s.num_subsets + 1
    assert all(a <= b for a, b in zip(s.bounds, s.bounds[1:]))


def test_device_loop_reduces_host_round_trips():
    """The point of the fused engine: O(1) blocking transfers per subset
    instead of O(sweeps x ~4).  Without an overflow replay the run
    fetches once for the initial count, once per CD subset loop and once
    per FD shape group, and nothing else."""
    g = GRAPH_CASES["powerlaw"]()
    _, s = tip_decompose(g, _cfg())
    assert s.overflow_fallbacks == 0
    assert s.device_loop_calls == s.num_subsets + s.fd_groups
    assert s.host_round_trips == 1 + s.num_subsets + s.fd_groups
    assert s.host_round_trips < s.rho_cd


@pytest.mark.slow
def test_device_loop_overflow_fallback_exact():
    """A deliberately tiny peel buffer forces the bucket-overflow path
    (host replays the oversized sweep, buffer doubles): still exact."""
    g = GRAPH_CASES["powerlaw"]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg(peel_width=8))
    np.testing.assert_array_equal(tb, tr)
    assert stats.overflow_fallbacks > 0


@pytest.mark.slow
def test_device_loop_matches_oracle_random():
    """Randomized equivalence: device-resident CD theta == BUP oracle."""
    rng = np.random.default_rng(123)
    for trial in range(5):
        n_u = int(rng.integers(5, 45))
        n_v = int(rng.integers(4, 30))
        a = rng.random((n_u, n_v)) < rng.uniform(0.05, 0.5)
        eu, ev = np.nonzero(a)
        g = BipartiteGraph.from_edges(n_u, n_v, eu, ev)
        tb, _ = bup_oracle(g)
        p = int(rng.integers(1, 9))
        tr, _ = tip_decompose(g, _cfg(num_partitions=p))
        np.testing.assert_array_equal(tb, tr, err_msg=f"trial {trial}")


@pytest.mark.slow
def test_sparse_backend_through_engine():
    """The block-sparse staircase backend (gathered-B peel updates, HUC
    recounts, counting) drives the full engine exactly."""
    g = GRAPH_CASES["powerlaw"]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg(backend="interpret_sparse"))
    np.testing.assert_array_equal(tb, tr)


@pytest.mark.slow
def test_parb_device_loop_equals_host():
    """ParB baseline: the device-resident min-schedule matches the BUP
    oracle, elides the terminal sweep, and needs more synchronization
    rounds than RECEIPT's range-peel CD on the same graph (the paper's
    sync-count argument)."""
    from repro.core.receipt import parb_tip_decompose

    g = GRAPH_CASES["vhub"]()
    tb, _ = bup_oracle(g)
    td, sd = parb_tip_decompose(g, _cfg())
    np.testing.assert_array_equal(tb, td)
    assert sd.elided_sweeps >= 1          # terminal sweep skips the kernel
    _, sr = tip_decompose(g, _cfg())
    assert sd.rho_cd > sr.rho_cd


@pytest.mark.slow
def test_parb_device_loop_sweep_cap_reenters():
    """A tiny max_sweeps forces repeated cap-exits of the device loop;
    the driver must re-enter, not silently return theta=0 for the
    survivors."""
    from repro.core.receipt import parb_tip_decompose

    g = GRAPH_CASES["er_small"]()
    tb, _ = bup_oracle(g)
    td, sd = parb_tip_decompose(g, _cfg(max_sweeps=3))
    np.testing.assert_array_equal(tb, td)
    assert sd.device_loop_calls > 1


# --------------------------------------------------------------------- #
# whole-graph single-dispatch CD (cd_dispatch="graph", ISSUE 3 tentpole)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("case", ["er_small", "powerlaw", "star",
                                  "empty_edges", "single_bfly"])
def test_cd_graph_dispatch_matches_oracle(case):
    """Whole-graph CD (findHi on device, ONE dispatch for all subsets)
    must stay exact end to end — with the on-device DGM compacting the
    residual graph at every subset boundary."""
    g = GRAPH_CASES[case]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg(cd_dispatch="graph"))
    np.testing.assert_array_equal(tb, tr)
    assert stats.dgm_compactions == 0          # no HOST compaction by design
    # on-device DGM runs at every closed subset boundary instead
    assert stats.dgm_device_compactions == stats.num_subsets


@pytest.mark.parametrize("case", ["er_small", "powerlaw", "vhub"])
def test_cd_graph_dispatch_dgm_off_still_exact(case):
    """use_dgm=False disables the on-device compaction branch entirely;
    supports are permutation-invariant, so theta must not move."""
    g = GRAPH_CASES[case]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg(cd_dispatch="graph", use_dgm=False))
    np.testing.assert_array_equal(tb, tr)
    assert stats.dgm_device_compactions == 0


def test_cd_graph_dgm_wedges_match_subset_driver():
    """The point of on-device DGM: the graph dispatch's traversed-wedge
    count (and HUC behavior, via the re-estimated c_rcnt) lands within
    10% of the per-subset DGM driver's — it no longer pays the
    whole-graph HUC bound for the entire run."""
    from repro.core.receipt import RunStats, receipt_cd

    g = GRAPH_CASES["vhub"]()
    res = {}
    for disp in ("subset", "graph"):
        stats = RunStats()
        receipt_cd(g, _cfg(num_partitions=16, cd_dispatch=disp), stats)
        res[disp] = stats
    assert res["graph"].wedges_cd <= res["subset"].wedges_cd * 1.10
    assert res["graph"].huc_recounts >= res["subset"].huc_recounts


def test_cd_graph_dispatch_o1_round_trips():
    """The tentpole claim: whole-graph CD blocks the host O(1) times per
    GRAPH — one sizing snapshot + one final fetch (+ a bounded overflow
    surcharge) — independent of the subset count."""
    from repro.core.receipt import RunStats, receipt_cd

    g = GRAPH_CASES["powerlaw"]()
    stats = RunStats()
    receipt_cd(g, _cfg(num_partitions=16, cd_dispatch="graph"), stats)
    assert stats.num_subsets > 4
    assert stats.host_round_trips <= 2 + 6 * stats.overflow_fallbacks
    sub = RunStats()
    receipt_cd(g, _cfg(num_partitions=16, cd_dispatch="subset"), sub)
    assert stats.host_round_trips < sub.host_round_trips


def test_cd_graph_dispatch_theorem1_containment():
    """Theorem 1 under device-side findHi: every vertex's tip number lies
    in its subset's range."""
    from repro.core.receipt import RunStats, receipt_cd

    g = GRAPH_CASES["vhub"]()
    stats = RunStats()
    subset_id, _isup, bounds, _ = receipt_cd(
        g, _cfg(num_partitions=8, cd_dispatch="graph"), stats)
    tb, _ = bup_oracle(g)
    for u in range(g.n_u):
        i = subset_id[u]
        assert bounds[i] <= tb[u] < bounds[i + 1], (
            f"u={u} theta={tb[u]} not in [{bounds[i]}, {bounds[i+1]})")


def test_cd_graph_dispatch_init_support_vector():
    """The on-device FD init snapshot (Lemma 1) equals the host path's."""
    from repro.core.peeling import shared_butterfly_matrix
    from repro.core.receipt import RunStats, receipt_cd

    g = GRAPH_CASES["er_small"]()
    stats = RunStats()
    subset_id, init_sup, _b, _ = receipt_cd(
        g, _cfg(num_partitions=4, cd_dispatch="graph"), stats)
    b2 = shared_butterfly_matrix(g)
    for i in range(subset_id.max() + 1):
        geq = subset_id >= i
        for u in np.where(subset_id == i)[0]:
            assert init_sup[u] == b2[u][geq].sum(), (u, i)


# --------------------------------------------------------------------- #
# graph-dispatch overflow replay under the DGM column permutation
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("case", ["powerlaw", "vhub"])
def test_cd_graph_overflow_replay_on_permuted_matrix(case):
    """A deliberately tiny peel buffer forces host_sweep re-entries AFTER
    on-device DGM boundaries have column-permuted the carried matrix —
    the replay must run against the carried graph (via _GraphStateView),
    not the stale construction-time DeviceGraph.  Exactness end to end
    proves the permutation-aware fold-back."""
    g = GRAPH_CASES[case]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg(cd_dispatch="graph", peel_width=8))
    np.testing.assert_array_equal(tb, tr)
    assert stats.overflow_fallbacks > 0        # the replay path actually ran
    assert stats.dgm_device_compactions > 0    # ... against a permuted matrix


@pytest.mark.slow
def test_cd_graph_overflow_replay_sparse_backend():
    """Same forced-overflow replay through the block-sparse staircase
    backend: the carried row_ext/kmax (re-tightened on device at every
    boundary) must stay consistent with the permuted matrix the replay's
    gathered-B kernel dispatch consumes."""
    g = GRAPH_CASES["powerlaw"]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(
        g, _cfg(cd_dispatch="graph", peel_width=8,
                backend="interpret_sparse", kernel_blocks=(8, 8, 16)))
    np.testing.assert_array_equal(tb, tr)
    assert stats.overflow_fallbacks > 0
    assert stats.dgm_device_compactions > 0


def test_cd_dispatch_and_valve_validation():
    from repro.core.receipt import RunStats, receipt_cd

    g = GRAPH_CASES["fig1"]()
    with pytest.raises(ValueError, match="cd_dispatch"):
        tip_decompose(g, _cfg(cd_dispatch="Graph"))
    with pytest.raises(ValueError, match="max_sweeps"):
        tip_decompose(g, _cfg(max_sweeps=0))
    with pytest.raises(ValueError, match="checkpoint"):
        receipt_cd(g, _cfg(cd_dispatch="graph"), RunStats(),
                   checkpoint_cb=lambda s: None)


# --------------------------------------------------------------------- #
# the max_sweeps CD valve (ISSUE 3 satellite / ROADMAP last item)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dispatch", ["subset", "graph"])
def test_cd_sweep_cap_reenters_and_preserves_containment(dispatch):
    """A capped CD subset must NOT close early: the driver re-enters the
    device loop on cap-exit (the valve bounds ONE invocation, never the
    schedule), so Theorem 1's range containment survives any cap >= 1 —
    the pre-fix behavior floored theta at a too-high subset bound."""
    from repro.core.receipt import RunStats, receipt_cd, receipt_fd

    g = GRAPH_CASES["er_small"]()
    tb, _ = bup_oracle(g)
    cfg = _cfg(num_partitions=4, max_sweeps=1, cd_dispatch=dispatch)
    stats = RunStats()
    sid, isup, bounds, _ = receipt_cd(g, cfg, stats)
    for u in range(g.n_u):
        assert bounds[sid[u]] <= tb[u] < bounds[sid[u] + 1], (dispatch, u)
    th = receipt_fd(g, sid, isup, bounds, cfg, stats)
    np.testing.assert_array_equal(np.round(th).astype(np.int64), tb)
    assert stats.device_loop_calls > stats.num_subsets


def test_cd_checkpoint_restart_exact():
    """Fault tolerance of the peeling engine itself: interrupt CD at a
    subset boundary, restore the checkpointed state (through the same
    CheckpointManager as train states), continue, and get EXACTLY the
    same tip numbers."""
    import tempfile

    from repro.core.receipt import RunStats, receipt_cd, receipt_fd
    from repro.train.checkpoint import CheckpointManager

    g = GRAPH_CASES["powerlaw"]()
    cfg = _cfg(num_partitions=8, degree_sort=False)

    # uninterrupted reference
    tb, _ = bup_oracle(g)

    # run 1: capture the state at the 3rd subset boundary, then "crash"
    class Stop(Exception):
        pass

    captured = {}

    def cb(state):
        if int(state["i"]) == 3:
            captured["state"] = state
            raise Stop()

    stats = RunStats()
    try:
        receipt_cd(g, cfg, stats, checkpoint_cb=cb)
        assert False, "expected interruption"
    except Stop:
        pass
    assert "state" in captured

    # persist + restore through the real checkpoint manager
    with tempfile.TemporaryDirectory() as d:
        ck = CheckpointManager(d)
        ck.save(3, captured["state"])
        restored = ck.restore(captured["state"])

    # run 2: resume from the restored state
    stats2 = RunStats()
    subset_id, init_sup, bounds, _ = receipt_cd(
        g, cfg, stats2, resume_state=restored
    )
    theta = receipt_fd(g, subset_id, init_sup, bounds, cfg, stats2)
    np.testing.assert_array_equal(np.round(theta).astype(np.int64), tb)


def test_v_side_decomposition():
    """side='V' peels the other vertex set (Table 3 *V rows)."""
    g = GRAPH_CASES["powerlaw"]()
    gt = BipartiteGraph.from_edges(g.n_v, g.n_u, g.edges_v, g.edges_u)
    tb, _ = bup_oracle(gt)
    tv, _ = tip_decompose(g, _cfg(), side="V")
    np.testing.assert_array_equal(tb, tv)
