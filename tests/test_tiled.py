"""Tiled-sparse representation (ISSUE 7): ``TiledGraph`` construction,
the nonzero-tile kernels (two-speed xla oracle + pallas), the tiled
whole-graph level-peel engine, and the Planner's cost-model routing.

The load-bearing claim is bit-identical theta: tip numbers are
canonical across exact peel schedules, so dense and tiled must agree
EXACTLY — any drift means a kernel or the monotone-level clamp broke.
"""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.api import EngineConfig, Planner, PlanInfeasibleError, decompose
from repro.core.engine.tiled import build_tiled, tiled_blocks
from repro.core.graph import (
    BipartiteGraph,
    TiledGraph,
    paper_fig1_graph,
    powerlaw_bipartite,
    random_bipartite,
)
from repro.core.peeling import bup_oracle
from repro.core.receipt import ReceiptConfig, tip_decompose
from repro.kernels import butterfly_tiled as ktiled
from repro.kernels import ops as kops

from conftest import GRAPH_CASES

SMALL_BLOCKS = (8, 8, 8)


def _cfg(**kw):
    base = dict(num_partitions=3, kernel_blocks=SMALL_BLOCKS,
                backend="xla")
    base.update(kw)
    return ReceiptConfig(**base)


def _er(nu, nv, ne, seed):
    rng = np.random.default_rng(seed)
    return BipartiteGraph.from_edges(
        nu, nv, rng.integers(0, nu, ne), rng.integers(0, nv, ne))


def _csr_dense(g: BipartiteGraph) -> np.ndarray:
    """Unpadded dense biadjacency rebuilt from the CSR arrays."""
    indptr, indices = g.csr_u()
    a = np.zeros((g.n_u, g.n_v), np.float32)
    for u in range(g.n_u):
        a[u, indices[indptr[u]:indptr[u + 1]]] = 1.0
    return a


def _update_ref(a: np.ndarray, s: np.ndarray) -> np.ndarray:
    """Dense oracle of the mask-form butterfly update."""
    w = a @ a.T
    b2 = w * (w - 1.0) * 0.5
    np.fill_diagonal(b2, 0.0)
    return b2 @ s


# --------------------------------------------------------------------- #
# TiledGraph construction
# --------------------------------------------------------------------- #
class TestTiledGraph:
    def test_dense_round_trip_fig1(self):
        g = paper_fig1_graph()
        tg = TiledGraph.from_graph(g, block_rows=8, block_k=8)
        assert (tg.dense()[:g.n_u, :g.n_v] == _csr_dense(g)).all()
        # padding region is all zero
        assert tg.dense()[g.n_u:].sum() == 0
        assert tg.dense()[:, g.n_v:].sum() == 0

    def test_structure_invariants(self):
        g = powerlaw_bipartite(200, 120, 1500, seed=5)
        tg = TiledGraph.from_graph(g, block_rows=8, block_k=8)
        # CSR-of-tiles discipline: srow non-decreasing, sptr covers all
        # slots, every row-tile owns >= 1 slot
        assert (np.diff(tg.srow) >= 0).all()
        assert tg.sptr[0] == 0 and tg.sptr[-1] == tg.n_slots
        assert (np.diff(tg.sptr) >= 1).all()
        # pos is the exact inverse of (srow, scol) for materialized tiles
        for slot in range(tg.n_slots):
            i, k = int(tg.srow[slot]), int(tg.scol[slot])
            if tg.pos[i, k] >= 0:
                assert tg.pos[i, k] == slot or (
                    tg.tile_data[slot] == 0).all()
        # every nonzero tile of the dense matrix is materialized
        d = tg.dense()
        bi, bk = tg.block_rows, tg.block_k
        for i in range(tg.n_row_tiles):
            for k in range(tg.n_col_tiles):
                blk = d[i * bi:(i + 1) * bi, k * bk:(k + 1) * bk]
                if blk.any():
                    assert tg.pos[i, k] >= 0

    def test_slot_padding_is_inert(self):
        g = random_bipartite(50, 30, 0.15, seed=3)
        tg = TiledGraph.from_graph(g, block_rows=8, block_k=8)
        padded = TiledGraph.from_graph(
            g, block_rows=8, block_k=8, pad_slots_to=tg.n_slots + 13)
        assert padded.n_slots == tg.n_slots + 13
        assert (padded.dense() == tg.dense()).all()
        # filler slots are zero payloads the liveness mask kills
        live = np.asarray(ktiled.slot_liveness(
            jnp.asarray(padded.tile_data)))
        assert live[tg.n_slots:].sum() == 0

    def test_byte_accounting(self):
        g = _er(512, 512, 2000, seed=9)
        tg = TiledGraph.from_graph(g, block_rows=8, block_k=8)
        assert tg.m == g.csr_u()[1].size
        assert tg.dense_bytes() == 4 * tg.rows_pad * tg.cols_pad
        # the sparse regime this representation exists for
        assert tg.tiled_bytes() < tg.dense_bytes()
        assert 0.0 < tg.fill_ratio() <= 1.0

    @settings(max_examples=20, deadline=None)
    @given(
        nu=st.integers(1, 60),
        nv=st.integers(1, 60),
        density=st.floats(0.0, 0.4),
        seed=st.integers(0, 10_000),
        br=st.sampled_from([4, 8, 16]),
        bk=st.sampled_from([4, 8, 16]),
    )
    def test_property_csr_round_trip(self, nu, nv, density, seed, br, bk):
        g = random_bipartite(nu, nv, density, seed=seed)
        tg = TiledGraph.from_graph(g, block_rows=br, block_k=bk)
        assert (tg.dense()[:nu, :nv] == _csr_dense(g)).all()
        assert tg.rows_pad % br == 0 and tg.cols_pad % bk == 0

    def test_rejects_non_multiple_padding(self):
        g = paper_fig1_graph()
        with pytest.raises(ValueError, match="block"):
            TiledGraph.from_graph(g, block_rows=8, block_k=8, rows_pad=12)


# --------------------------------------------------------------------- #
# tiled kernels: two-speed xla oracle, pallas kernel, masked colsum
# --------------------------------------------------------------------- #
def _tiled_args(g, blocks=(8, 8)):
    tg = TiledGraph.from_graph(g, block_rows=blocks[0], block_k=blocks[1])
    td = jnp.asarray(tg.tile_data)
    return tg, (td, jnp.asarray(tg.srow), jnp.asarray(tg.scol),
                jnp.asarray(tg.sptr), jnp.asarray(tg.pos),
                ktiled.slot_liveness(td))


def _masks(rows_pad, seed=0):
    """Mask battery spanning the gathered-row (<= 16 nonzero rows) and
    band-streaming paths of the two-speed xla oracle, including both
    sides of the exact path boundary."""
    rng = np.random.default_rng(seed)
    out = {
        "zero": np.zeros(rows_pad, np.float32),
        "single": np.eye(1, rows_pad, 2, dtype=np.float32).ravel(),
        "all": np.ones(rows_pad, np.float32),
        "sparse": (rng.random(rows_pad) < 0.05).astype(np.float32),
        "dense_mask": (rng.random(rows_pad) < 0.5).astype(np.float32),
    }
    for width in (16, 17):       # _PEEL_ROW_WIDTH boundary
        if rows_pad >= width:
            m = np.zeros(rows_pad, np.float32)
            m[rng.choice(rows_pad, size=width, replace=False)] = 1.0
            out[f"w{width}"] = m
    return out


@pytest.mark.parametrize("case", ["fig1", "er_small", "powerlaw",
                                  "empty_edges", "star"])
def test_tiled_update_xla_matches_dense_ref(case):
    g = GRAPH_CASES[case]()
    tg, args = _tiled_args(g)
    a = tg.dense()
    for name, s in _masks(tg.rows_pad, seed=11).items():
        got = np.asarray(ktiled.butterfly_update_tiled_xla(*args, s))
        want = _update_ref(a, s)
        assert np.array_equal(got, want), (case, name)


@pytest.mark.parametrize("case", ["fig1", "er_small", "powerlaw"])
def test_tiled_update_pallas_interpret_matches_xla(case):
    g = GRAPH_CASES[case]()
    tg, args = _tiled_args(g)
    for name, s in _masks(tg.rows_pad, seed=13).items():
        sj = jnp.asarray(s)
        xla = np.asarray(kops.butterfly_update_tiled(
            *args, sj, backend="xla"))
        interp = np.asarray(kops.butterfly_update_tiled(
            *args, sj, backend="interpret"))
        assert np.array_equal(xla, interp), (case, name)


def test_masked_colsum_matches_dense_ref():
    g = powerlaw_bipartite(200, 120, 1500, seed=5)
    tg, (td, srow, scol, _sptr, pos, _sl) = _tiled_args(g)
    a = tg.dense()
    for name, s in _masks(tg.rows_pad, seed=17).items():
        got = np.asarray(ktiled.masked_colsum_tiled(td, srow, scol, pos,
                                                    jnp.asarray(s)))
        assert np.array_equal(got, s @ a), name


def test_regather_zeroes_dead_rows_and_cols():
    g = random_bipartite(40, 25, 0.3, seed=4)
    tg, (td, srow, scol, _sptr, _pos, _sl) = _tiled_args(g)
    rng = np.random.default_rng(21)
    alive = (rng.random(tg.rows_pad) < 0.6).astype(np.float32)
    colf = (rng.random(tg.cols_pad) < 0.6).astype(np.float32)
    td2, _sl2 = ktiled.regather_tiles(td, srow, scol, jnp.asarray(alive),
                                      jnp.asarray(colf))
    want = tg.dense() * alive[:, None] * colf[None, :]
    # reassemble the regathered tiles into dense form
    bi, bk = tg.block_rows, tg.block_k
    got = np.zeros_like(want)
    td2h = np.asarray(td2)
    for slot in range(tg.n_slots):
        i, k = int(tg.srow[slot]), int(tg.scol[slot])
        got[i * bi:(i + 1) * bi, k * bk:(k + 1) * bk] += td2h[slot]
    assert np.array_equal(got, want)


# --------------------------------------------------------------------- #
# tiled engine: bit-identical theta vs dense pipeline and the oracle
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("case", sorted(GRAPH_CASES))
def test_engine_dense_tiled_bit_identical(case):
    g = GRAPH_CASES[case]()
    td_dense, _ = tip_decompose(g, _cfg(representation="dense"))
    td_tiled, _ = tip_decompose(g, _cfg(representation="tiled"))
    assert (td_dense == td_tiled).all()
    theta, _ = bup_oracle(g)
    assert (td_tiled == theta).all()


@pytest.mark.parametrize("side", ["U", "V"])
def test_engine_tiled_both_sides(side):
    g = powerlaw_bipartite(150, 90, 1100, seed=7)
    got, _ = tip_decompose(g, _cfg(representation="tiled"), side=side)
    ref = bup_oracle(g if side == "U" else g.transposed())[0]
    assert (got == ref).all()


def test_engine_tiled_monotone_level_regression():
    # many distinct peel levels + heavy hubs: the graph family that
    # exposed the missing Alg. 2 line 13 clamp (supports of survivors
    # must cap at the running level, or a later sweep's min drops below
    # an already-recorded theta)
    g = powerlaw_bipartite(400, 150, 4000, seed=23)
    got, _ = tip_decompose(g, _cfg(representation="tiled"))
    assert (got == bup_oracle(g)[0]).all()


@pytest.mark.parametrize("every,ratio", [(1, 0.9), (2, 0.5), (64, 0.0)])
def test_engine_tiled_recompaction_cadence_exact(every, ratio):
    # aggressive host recompaction (rebuild nearly every segment) and
    # fully disabled recompaction must both land on the oracle exactly —
    # carried supports are the loop's clamped values, never recounted
    g = powerlaw_bipartite(200, 120, 1500, seed=5)
    cfg = _cfg(representation="tiled", tiled_compact_every=every,
               tiled_compact_ratio=ratio)
    got, stats = tip_decompose(g, cfg)
    assert (got == bup_oracle(g)[0]).all()
    if every == 1 and ratio == 0.9:
        # the aggressive schedule must actually recompact (first
        # compaction is the host DGM pre-pass, so strictly more than 1)
        assert stats.dgm_compactions > 1


def test_engine_tiled_valve_reentry_exact():
    # max_sweeps valve trips mid-peel; the host driver re-enters with
    # carried state and must still be exact
    g = powerlaw_bipartite(200, 120, 1500, seed=5)
    got, stats = tip_decompose(
        g, _cfg(representation="tiled", max_sweeps=3))
    assert (got == bup_oracle(g)[0]).all()
    assert stats.device_loop_calls > 1


@pytest.mark.parametrize("dispatch", ["subset", "graph"])
def test_engine_tiled_matches_dense_cd_dispatch(dispatch):
    # the tiled engine has no CD phase; it must agree with the dense
    # pipeline under EITHER of its CD dispatch modes (theta canonicity)
    g = powerlaw_bipartite(150, 90, 1100, seed=7)
    dense, _ = tip_decompose(
        g, _cfg(representation="dense", cd_dispatch=dispatch))
    tiled, _ = tip_decompose(g, _cfg(representation="tiled"))
    assert (dense == tiled).all()


@pytest.mark.parametrize("case", ["fig1", "er_small"])
def test_engine_tiled_interpret_backend_exact(case):
    g = GRAPH_CASES[case]()
    got, _ = tip_decompose(
        g, _cfg(representation="tiled", backend="interpret"))
    assert (got == bup_oracle(g)[0]).all()


def test_tiled_blocks_and_build():
    cfg = _cfg()
    assert tiled_blocks(cfg) == (8, 8)
    g = random_bipartite(50, 30, 0.15, seed=3)
    tg = build_tiled(g, cfg)
    assert tg.rows_pad >= g.n_u and tg.cols_pad >= g.n_v


# --------------------------------------------------------------------- #
# Planner routing (cost model + memory admission)
# --------------------------------------------------------------------- #
class TestRepresentationRouting:
    def test_small_dense_graph_routes_dense(self):
        g = random_bipartite(50, 30, 0.15, seed=3)
        plan = Planner(EngineConfig(representation="auto")).plan(g)
        assert plan.representation == "dense"

    def test_memory_admission_overrides_crossover(self):
        # dense padded matrix ~16 MiB; a 12 MiB budget forces tiled even
        # though the occupancy crossover alone would keep this dense
        g = _er(2048, 2048, 10_000, seed=31)
        cfg = EngineConfig(representation="auto",
                           memory_budget_bytes=12 << 20,
                           num_partitions=3, kernel_blocks=SMALL_BLOCKS,
                           backend="xla")
        plan = Planner(cfg).plan(g)
        assert plan.representation == "tiled"
        assert plan.cost_model["tiled_bytes"] <= 12 << 20

    def test_forced_tiled_is_honored(self):
        g = random_bipartite(50, 30, 0.15, seed=3)
        plan = Planner(EngineConfig(representation="tiled")).plan(g)
        assert plan.representation == "tiled"

    @pytest.mark.parametrize("backend,representation,expect", [
        ("pallas", "tiled", "refused"),
        ("pallas_sparse", "tiled", "refused"),
        ("pallas", "auto", "dense"),
        ("interpret", "tiled", "tiled"),
        ("xla", "tiled", "tiled"),
    ])
    def test_tiled_kernel_past_chip_tables(self, backend, representation,
                                           expect):
        # 1024 row bands x 256 column bands of 8x8 tiles: the compiled
        # kernel's reverse map alone is 1 MiB of SMEM, past one v5e core;
        # the interpreter and the streaming xla form keep no such table
        n_u, n_v = 8192, 2048
        v = np.arange(n_v)
        g = BipartiteGraph.from_edges(n_u, n_v, np.concatenate([v, v + n_v]),
                                      np.concatenate([v, v]))
        cfg = EngineConfig(representation=representation, backend=backend,
                           num_partitions=3, kernel_blocks=SMALL_BLOCKS)
        if expect == "refused":
            with pytest.raises(PlanInfeasibleError, match="SMEM"):
                Planner(cfg).plan(g)
            return
        plan = Planner(cfg).plan(g)
        assert plan.representation == expect
        assert plan.cost_model["tiled_kernel_smem_bytes"] > ktiled.SMEM_BYTES

    def test_plan_dict_exposes_cost_model(self):
        g = random_bipartite(50, 30, 0.15, seed=3)
        d = Planner(EngineConfig(representation="auto")).plan(g).to_dict()
        assert d["representation"] in ("dense", "tiled")
        cm = d["cost_model"]
        for key in ("requested", "dense_bytes", "dense_cells",
                    "tiled_bytes", "tile_occupancy"):
            assert key in cm, key

    def test_memory_smoke_verify_above_dense_budget(self):
        # end-to-end: a sparse graph whose dense biadjacency exceeds the
        # budget decomposes tiled, and verify=True checks theta against
        # the host float64 oracle invariants
        g = _er(2048, 2048, 10_000, seed=31)
        cfg = EngineConfig(representation="auto",
                           memory_budget_bytes=12 << 20,
                           num_partitions=3, kernel_blocks=SMALL_BLOCKS,
                           backend="xla")
        res = decompose(g, cfg, verify=True)
        assert res.plan.representation == "tiled"
        assert (res.theta >= 0).all()


# --------------------------------------------------------------------- #
# subprocess equivalence: dense vs tiled in a fresh interpreter
# --------------------------------------------------------------------- #
_EQUIV_SCRIPT = r"""
import sys, json
sys.path.insert(0, "src")
import numpy as np
from repro.core.graph import powerlaw_bipartite
from repro.core.receipt import ReceiptConfig, tip_decompose

g = powerlaw_bipartite(256, 128, 2500, seed=2)
cfg = dict(num_partitions=3, kernel_blocks=(8, 8, 8), backend="xla")
dense, _ = tip_decompose(g, ReceiptConfig(representation="dense", **cfg))
tiled, _ = tip_decompose(g, ReceiptConfig(representation="tiled", **cfg))
print(json.dumps({
    "identical": bool((dense == tiled).all()),
    "max_theta": int(dense.max()),
}))
"""


def test_subprocess_dense_tiled_equivalence():
    res = subprocess.run(
        [sys.executable, "-c", _EQUIV_SCRIPT],
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["identical"]
    assert out["max_theta"] > 0
