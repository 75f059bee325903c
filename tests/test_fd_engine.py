"""FD level-peel engine: equivalence with the BUP oracle on CD's
partition, counter semantics, kernel-path fallbacks, and the scheduler's
Graham bound."""
import dataclasses
import itertools

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from scipy.sparse import csr_matrix

from repro.core.graph import (
    BipartiteGraph,
    paper_fig1_graph,
    powerlaw_bipartite,
)
from repro.core.peeling import bup_oracle, butterfly_supports
from repro.core.receipt import ReceiptConfig, RunStats, receipt_cd, receipt_fd
from repro.core.engine import tip_decompose
from repro.core.engine.fd import _level_delta, build_fd_tasks, pre_peel_tasks
from repro.core.scheduler import lpt_assign

from conftest import GRAPH_CASES

SMALL_BLOCKS = (8, 8, 8)


def _cfg(**kw):
    base = dict(
        num_partitions=6, kernel_blocks=SMALL_BLOCKS, backend="xla"
    )
    base.update(kw)
    return ReceiptConfig(**base)


def _fd_on_cd(g, cfg):
    """Run CD, then FD on its partition: (FD theta, FD-only stats)."""
    sid, init_sup, bounds, _ = receipt_cd(g, cfg, RunStats())
    stats = RunStats()
    return receipt_fd(g, sid, init_sup, bounds, cfg, stats), stats


# --------------------------------------------------------------------- #
# level-peel on CD's partition vs the BUP oracle (identical theta)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("case", ["powerlaw", "fig1"])
def test_level_peel_equals_legacy_peels(case):
    """The batched level-peel reproduces the exact BUP tip numbers on
    CD's partition: each independent subset peels to its true theta."""
    g = GRAPH_CASES[case]()
    tb, _ = bup_oracle(g)
    th, _ = _fd_on_cd(g, _cfg())
    np.testing.assert_array_equal(th, tb)


@pytest.mark.parametrize("case", ["vhub", "er_dense", "star"])
def test_level_peel_equals_legacy_more_shapes(case):
    g = GRAPH_CASES[case]()
    tb, _ = bup_oracle(g)
    th, _ = _fd_on_cd(g, _cfg(num_partitions=4))
    np.testing.assert_array_equal(th, tb)


@pytest.mark.parametrize("case", ["powerlaw", "vhub", "er_dense"])
def test_fd_modes_match_bup_end_to_end(case):
    g = GRAPH_CASES[case]()
    tb, _ = bup_oracle(g)
    tr, _ = tip_decompose(g, _cfg())
    np.testing.assert_array_equal(tb, tr)


@settings(max_examples=15, deadline=None)
@given(
    n_u=st.integers(4, 35),
    n_v=st.integers(3, 25),
    density=st.floats(0.05, 0.5),
    p=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_level_peel_equals_bup(n_u, n_v, density, p, seed):
    rng = np.random.default_rng(seed)
    a = rng.random((n_u, n_v)) < density
    eu, ev = np.nonzero(a)
    g = BipartiteGraph.from_edges(n_u, n_v, eu, ev)
    tb, _ = bup_oracle(g)
    tr, _ = tip_decompose(g, _cfg(num_partitions=p))
    np.testing.assert_array_equal(tb, tr)


# --------------------------------------------------------------------- #
# counter semantics (ISSUE 2 satellite: rho_fd / wedges_fd no longer
# static placeholders)
# --------------------------------------------------------------------- #
def test_level_peel_counters_are_dynamic():
    g = GRAPH_CASES["powerlaw"]()
    _, stats = _fd_on_cd(g, _cfg())
    n_peeled = int(sum(stats.subset_sizes))
    static_bound = int(sum(stats.subset_wedges_fd))
    assert stats.rho_fd > 0
    # level-peel sweeps <= sequential steps (one level >= one vertex)
    assert stats.rho_fd <= n_peeled
    # dynamically traversed wedges never exceed the static induced bound
    assert 0 < stats.wedges_fd <= static_bound
    assert stats.fd_groups > 0
    assert 0.0 <= stats.fd_padding_waste < 1.0


def test_level_peel_one_sync_per_group():
    """The level-peel runtime must sync the host exactly once per shape
    group (theta + counters ride back in the same device_get)."""
    g = GRAPH_CASES["powerlaw"]()
    cfg = _cfg()
    stats = RunStats()
    sid, init_sup, bounds, _ = receipt_cd(g, cfg, stats)
    before = stats.host_round_trips
    receipt_fd(g, sid, init_sup, bounds, cfg, stats)
    assert stats.host_round_trips - before == stats.fd_groups


def test_level_peel_tiny_gather_buffer_falls_back_on_device():
    """A deliberately tiny peel buffer forces the mask-form kernel
    fallback (an on-device lax.cond, never a host replay): still exact,
    and no overflow fallbacks are recorded."""
    g = GRAPH_CASES["powerlaw"]()
    cfg = _cfg()
    stats = RunStats()
    sid, init_sup, bounds, _ = receipt_cd(g, cfg, stats)
    want = receipt_fd(g, sid, init_sup, bounds, cfg, RunStats())
    tiny = dataclasses.replace(cfg, peel_width=8)
    tiny_stats = RunStats()
    got = receipt_fd(g, sid, init_sup, bounds, tiny, tiny_stats)
    np.testing.assert_array_equal(want, got)
    assert tiny_stats.overflow_fallbacks == 0


def test_level_peel_sweep_cap_reenters():
    """A tiny max_sweeps caps ONE loop invocation, not the schedule: the
    level driver must re-enter until every subset drains — survivors must
    not silently keep theta=0.  CD re-enters under the same cap, so its
    partition stays range-contained and FD must still reach the exact
    BUP tip numbers."""
    for seed in range(4):
        rng = np.random.default_rng(seed)
        a = rng.random((30, 20)) < 0.3
        eu, ev = np.nonzero(a)
        g = BipartiteGraph.from_edges(30, 20, eu, ev)
        tb, _ = bup_oracle(g)
        for ms in (1, 2, 3):
            th, _ = _fd_on_cd(g, _cfg(num_partitions=4, max_sweeps=ms))
            np.testing.assert_array_equal(th, tb,
                                          err_msg=f"seed={seed} ms={ms}")


def test_unknown_fd_mode_raises():
    """The FD engine has no modes: a stored config that still names the
    removed ``fd_mode`` knob fails loudly, naming the key."""
    from repro.api import EngineConfig

    with pytest.raises(ValueError, match="fd_mode"):
        EngineConfig.from_dict({"fd_mode": "level"})


def test_level_peel_interpret_backend():
    """The grouped Pallas kernel entry point (interpreter) drives FD
    exactly."""
    g = GRAPH_CASES["er_small"]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(
        g, _cfg(backend="interpret", kernel_blocks=(8, 8, 16)))
    np.testing.assert_array_equal(tb, tr)
    assert stats.rho_fd > 0


def test_level_peel_sparse_backend():
    """The batched staircase kernel (per-group extents) drives FD
    exactly."""
    g = GRAPH_CASES["powerlaw"]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg(backend="interpret_sparse"))
    np.testing.assert_array_equal(tb, tr)
    assert stats.rho_fd > 0


def test_level_peel_no_overlap_matches():
    """Double-buffered group dispatch is a pure latency optimization: on
    a graph with at least two shape groups (so one group is in flight
    while the next is built) theta is the exact BUP answer."""
    g = GRAPH_CASES["vhub"]()
    tb, _ = bup_oracle(g)
    tr, stats = tip_decompose(g, _cfg())
    assert stats.fd_groups >= 2
    np.testing.assert_array_equal(tb, tr)


# --------------------------------------------------------------------- #
# scheduler: Graham's 4/3 bound for lpt_assign
# --------------------------------------------------------------------- #
def _makespan(weights, assign):
    return max((sum(weights[i] for i in a) for a in assign), default=0.0)


def _opt_makespan(weights, k):
    """Brute-force optimum over all k^n assignments (small n only)."""
    best = float("inf")
    n = len(weights)
    for combo in itertools.product(range(k), repeat=n):
        loads = [0.0] * k
        for i, j in enumerate(combo):
            loads[j] += weights[i]
        best = min(best, max(loads))
    return best


@settings(max_examples=30, deadline=None)
@given(
    k=st.integers(2, 3),
    weights=st.lists(st.integers(1, 50), min_size=1, max_size=8),
)
def test_property_lpt_respects_graham_bound(k, weights):
    """Graham [1969]: LPT makespan <= (4/3 - 1/(3k)) * OPT."""
    weights = [float(w) for w in weights]
    assign = lpt_assign(weights, k)
    got = _makespan(weights, assign)
    opt = _opt_makespan(weights, k)
    assert got <= (4.0 / 3.0 - 1.0 / (3.0 * k)) * opt + 1e-9
    # sanity: every task assigned exactly once
    seen = sorted(i for a in assign for i in a)
    assert seen == list(range(len(weights)))


def test_lpt_init_loads_carry_across_batches():
    """Cross-group load carryover (the mesh FD driver dispatches one LPT
    plan per shape group): seeding the loads steers the next batch away
    from already-loaded workers — without it every batch front-loads
    worker 0."""
    first = lpt_assign([8.0], 2)
    assert first == [[0], []]
    second = lpt_assign([8.0], 2, init_loads=[8.0, 0.0])
    assert second == [[], [0]]
    # default (no seed) is unchanged legacy behavior
    assert lpt_assign([8.0], 2, init_loads=None) == [[0], []]


# --------------------------------------------------------------------- #
# host pre-peel: the wedge-list level delta against the dense product
# --------------------------------------------------------------------- #
def _dense_pre_peel_tasks(tasks, init_support, theta, stats, levels=1):
    """``pre_peel_tasks`` as it was with the dense float64 delta: the
    reference the wedge-list delta must reproduce bit for bit."""
    levels = max(int(levels), 1)
    out = []
    for t in tasks:
        mems, sub, lo = t["members"], t["sub"], t["lo"]
        sup = np.asarray(init_support[mems], np.float64).copy()
        n = len(mems)
        alive = np.ones(n, bool)
        dv_cur = np.bincount(sub.edges_v, minlength=sub.n_v)
        a_host = None
        done = False
        for lvl in range(levels):
            cap_l = (max(float(sup[alive].min()), lo) if alive.any()
                     else lo)
            l_mask = alive & (sup <= cap_l)
            theta[mems[l_mask]] = cap_l
            peel_e = l_mask[sub.edges_u]
            colsum = np.bincount(sub.edges_v[peel_e], minlength=sub.n_v)
            stats.wedges_fd += int(
                (colsum * np.maximum(dv_cur - 1, 0)).sum())
            stats.rho_fd += 1
            surv_mask = alive & ~l_mask
            if not surv_mask.any():
                done = True
                break
            if lvl == levels - 1:
                out.append(dict(
                    t, surv=np.where(surv_mask)[0],
                    l1=np.where(l_mask)[0], cap1=cap_l,
                    sup_surv=sup[surv_mask],
                ))
                done = True
                break
            if a_host is None:
                a_host = np.zeros((n, sub.n_v), np.float64)
                a_host[sub.edges_u, sub.edges_v] = 1.0
            w = a_host[surv_mask] @ a_host[l_mask].T
            delta = (w * (w - 1.0) * 0.5).sum(axis=1)
            sup[surv_mask] = np.maximum(sup[surv_mask] - delta, cap_l)
            a_host[l_mask] = 0.0
            dv_cur = dv_cur - colsum
            alive = surv_mask
        if not done and alive.any():
            out.append(dict(
                t, surv=np.where(alive)[0], l1=np.zeros(0, np.int64),
                cap1=lo, sup_surv=sup[alive],
            ))
    return out


def _cd_fd_inputs(g, num_partitions):
    """FD tasks and supports of ``g`` from a real CD partition."""
    cfg = _cfg(num_partitions=num_partitions)
    stats = RunStats()
    sid, init_sup, bounds, _ = receipt_cd(g, cfg, stats)
    return build_fd_tasks(g, sid, bounds, RunStats()), init_sup


def _bicliques(sizes):
    """Disjoint complete bipartite blocks ``K_{a,b}`` for each ``(a, b)``."""
    eu, ev, u0, v0 = [], [], 0, 0
    for a, b in sizes:
        for u, v in itertools.product(range(a), range(b)):
            eu.append(u0 + u)
            ev.append(v0 + v)
        u0, v0 = u0 + a, v0 + b
    return BipartiteGraph.from_edges(u0, v0, eu, ev)


def _whole_u_task(g):
    """One FD task holding every U vertex, at its butterfly support."""
    sup = butterfly_supports(g).astype(np.float64)
    members = np.arange(g.n_u)
    sub, _ = g.induced_on_u(members)
    return [dict(members=members, sub=sub, lo=0.0, wedges=0)], sup


_PREPEEL_CASES = {
    # a CD catch-all subset: the first level is the bulk of its rows
    "powerlaw": lambda: _cd_fd_inputs(
        powerlaw_bipartite(400, 200, 3000, seed=5), 6),
    # three support levels in one subset: drains on the host from
    # levels=4 on, hands survivors over below it
    "drains": lambda: _whole_u_task(_bicliques([(2, 2), (3, 3), (4, 4)])),
    # every row shares one support: the first level leaves no survivor
    "zero_survivors": lambda: _whole_u_task(_bicliques([(4, 5)])),
}


@pytest.mark.parametrize("levels", [1, 2, 4, 8])
@pytest.mark.parametrize("case", sorted(_PREPEEL_CASES))
def test_pre_peel_wedge_delta_equals_dense(case, levels):
    tasks, init_sup = _PREPEEL_CASES[case]()
    n_u = len(init_sup)
    runs = []
    for fn in (_dense_pre_peel_tasks, pre_peel_tasks):
        theta, stats = np.zeros(n_u), RunStats()
        out = fn(tasks, init_sup, theta, stats, levels=levels)
        runs.append((out, theta, stats))
    (want, th_want, st_want), (got, th_got, st_got) = runs

    np.testing.assert_array_equal(th_got, th_want)
    assert (st_got.rho_fd, st_got.wedges_fd) == (st_want.rho_fd,
                                                 st_want.wedges_fd)
    assert len(got) == len(want)
    for g_t, w_t in zip(got, want):
        assert g_t["members"] is w_t["members"]
        for key in ("surv", "l1", "sup_surv"):
            np.testing.assert_array_equal(g_t[key], w_t[key], err_msg=key)
        assert g_t["cap1"] == w_t["cap1"]
    if case == "drains":
        assert len(got) == (0 if levels >= 3 else 1)
    if case == "zero_survivors":
        assert got == [] and st_got.fd_prepeel_pairs == 0
    if case == "powerlaw" and levels > 1:
        assert st_got.fd_prepeel_pairs > 0


def _brute_delta(rows, surv, peeled):
    """``sum_{x in L} C(|N(u) & N(x)|, 2)`` per survivor, by sets."""
    return np.array([
        sum(len(rows[u] & rows[x]) * (len(rows[u] & rows[x]) - 1) // 2
            for x in peeled)
        for u in surv], np.int64)


# row 2 shares no column (its column 4 has degree 1); row 5 shares only
# column 3; rows 0, 1, 3, 4 overlap in two or more columns
_HAND_ROWS = [{0, 1, 2}, {0, 1}, {4}, {1, 2, 3}, {0, 1, 2, 3}, {3}]


@pytest.mark.parametrize("rows, peeled", [
    (_HAND_ROWS, [0, 3]),
    (_HAND_ROWS, [4]),
    (_HAND_ROWS, [2]),
    (_HAND_ROWS, [1, 2, 5]),
    ([{0}, {0}, {1}], [0]),
    ([{0, 1, 2}, {0, 1, 2}, {0, 1, 2}, {0, 1}], [0, 1]),
], ids=["two-peeled", "hub-peeled", "isolated-peeled", "sparse-peeled",
        "no-butterfly", "dense"])
def test_level_delta_equals_brute_force(rows, peeled):
    n, n_v = len(rows), 1 + max(max(r) for r in rows)
    eu = [u for u, r in enumerate(rows) for _ in r]
    ev = [v for r in rows for v in sorted(r)]
    a = csr_matrix((np.ones(len(eu), np.int64), (eu, ev)), shape=(n, n_v))
    l_mask = np.isin(np.arange(n), peeled)
    surv = np.flatnonzero(~l_mask)
    got = _level_delta(a, ~l_mask, l_mask)
    assert got.dtype == np.int64
    np.testing.assert_array_equal(got, _brute_delta(rows, surv, peeled))
