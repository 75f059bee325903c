"""``DeviceGraph`` builds its dense residual biadjacency on the device from
the induced edge list.  Every product of a build must be bit for bit what
the host dense build gives: ``_host_build`` below is that build, kept
here as the reference."""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.engine.peel_loop as peel_loop
from repro.core.engine import DeviceGraph, ReceiptConfig, RunStats, bucket
from repro.core.graph import BipartiteGraph, powerlaw_bipartite
from repro.kernels.butterfly_sparse import row_extents

BLOCKS = (8, 8, 8)


def _host_build(g, members, cfg, plan=None):
    """The host dense build: induce, fill a dense matrix on the host, take
    the extents with a dense pass over it."""
    bi, bj, bk = cfg.kernel_blocks
    sub, _ = g.induced_on_u(members, min_degree_v=2)
    dvk = sub.degrees_v()
    eu, ev = sub.edges_u, sub.edges_v
    n_cols = max(int(sub.n_v), 1)
    rows_pad = bucket(len(members), max(bi, bj))
    cols_pad = bucket(n_cols, bk)
    if plan is not None:
        rows_pad = plan.quantize_dim("dgm_rows", rows_pad)
        cols_pad = plan.quantize_dim("dgm_cols", cols_pad)
    a = np.zeros((rows_pad, cols_pad), np.float32)
    a[eu, ev] = 1.0
    dv_pad = np.zeros(cols_pad, np.float32)
    dv_pad[: len(dvk)] = dvk
    w = np.zeros(rows_pad, np.float64)
    np.add.at(w, eu, (dvk[ev] - 1).astype(np.float64))
    du = np.bincount(eu, minlength=rows_pad)
    rext = row_extents(a, bk)
    return dict(
        rows_pad=rows_pad, cols_pad=cols_pad, n_rows=len(members),
        n_cols=n_cols,
        a=np.asarray(jnp.asarray(a, dtype=cfg.dtype)),
        dv0=dv_pad, w_np=w, w=np.asarray(jnp.asarray(w, dtype=cfg.dtype)),
        total_wedges=float(w.sum()),
        c_rcnt=float(np.minimum(du[eu], dvk[ev]).sum()),
        row_ext=rext, kmax=rext.reshape(-1, bi).max(axis=1),
    )


class _Floors:
    """``ExecutionPlan.quantize_dim`` over fixed shape floors."""

    def __init__(self, **floors):
        self.floors = floors

    def quantize_dim(self, name, value):
        fits = [v for v in self.floors.get(name, ()) if v >= value]
        return min(fits) if fits else int(value)


def _graph(edges, n_u, n_v):
    eu, ev = zip(*edges) if edges else ((), ())
    return BipartiteGraph.from_edges(n_u, n_v, eu, ev)


_POWERLAW = powerlaw_bipartite(120, 70, 800, seed=7)
_RNG = np.random.default_rng(3)
# the survivors of a DGM boundary, in the order the residual graph kept them
_RESIDUAL = _RNG.permutation(120)[:45]
# rows 0-3 reach only column 5 (degree 4); rows 4-5 only degree-1 columns,
# so they keep no edge; row 6 has none at all
_EMPTY_ROWS = _graph([(0, 5), (1, 5), (2, 5), (3, 5), (0, 2), (1, 2),
                      (4, 0), (5, 1)], 7, 6)
# columns 0-3 have degree 1 and are dropped; 4 and 9 survive
_DEGREE_ONE = _graph([(0, 0), (1, 1), (2, 2), (3, 3), (0, 4), (1, 4),
                      (2, 4), (1, 9), (3, 9)], 4, 10)
# 16 columns of degree 2: the last (15) lies in the last 8-wide stripe
_LAST_STRIPE = _graph([(v % 6, v) for v in range(16)]
                      + [((v + 1) % 6, v) for v in range(16)], 6, 16)
# about 550 columns kept, padded to 640 = five 128-wide tiles: the
# scatter's tile order differs from the (u, v) order
_WIDE = powerlaw_bipartite(60, 900, 5000, seed=11)

CASES = {
    "powerlaw-all": (_POWERLAW, np.arange(120), None),
    "residual-out-of-order": (_POWERLAW, _RESIDUAL, None),
    "rows-without-edges": (_EMPTY_ROWS, np.arange(7), None),
    "degree-one-columns": (_DEGREE_ONE, np.arange(4), None),
    "quantized-pads": (_POWERLAW, _RESIDUAL,
                       _Floors(dgm_rows=[256], dgm_cols=[128],
                               dgm_edges=[8192])),
    "no-edges": (_DEGREE_ONE, np.array([3, 0]), None),
    "last-stripe": (_LAST_STRIPE, np.arange(6), None),
    "tile-order": (_WIDE, np.arange(60), _Floors(dgm_cols=[640])),
}


@pytest.mark.parametrize("case", list(CASES))
def test_device_build_equals_host_dense_build(case):
    g, members, plan = CASES[case]
    cfg = ReceiptConfig(kernel_blocks=BLOCKS, backend="xla")
    want = _host_build(g, members, cfg, plan)
    dg = DeviceGraph(g, members, cfg, plan=plan)

    for key in ("rows_pad", "cols_pad", "n_rows", "n_cols",
                "total_wedges", "c_rcnt"):
        assert getattr(dg, key) == want[key], key
    for key in ("a", "dv0", "w_np", "w", "row_ext", "kmax"):
        got = np.asarray(getattr(dg, key))
        assert got.dtype == want[key].dtype, key
        np.testing.assert_array_equal(got, want[key], err_msg=key)
    np.testing.assert_array_equal(np.asarray(dg.ids), np.arange(dg.rows_pad))
    np.testing.assert_array_equal(dg.members, members)


def test_case_shapes_cover_what_they_name():
    cfg = ReceiptConfig(kernel_blocks=BLOCKS, backend="xla")
    dg = DeviceGraph(*CASES["rows-without-edges"][:2], cfg)
    assert list(np.asarray(dg.row_ext)[:7]) == [1, 1, 1, 1, 0, 0, 0]
    dg = DeviceGraph(*CASES["no-edges"][:2], cfg)
    assert dg.n_cols == 1 and not np.asarray(dg.a).any()
    dg = DeviceGraph(*CASES["last-stripe"][:2], cfg)
    assert dg.cols_pad == 16 and int(np.asarray(dg.kmax).max()) == 2
    g, members, plan = CASES["quantized-pads"]
    dg = DeviceGraph(g, members, cfg, plan=plan)
    assert (dg.rows_pad, dg.cols_pad) == (256, 128)
    assert dg.rows_pad > bucket(len(members), 8)


@pytest.mark.parametrize("case", list(CASES))
def test_scatter_indices_are_in_bounds_sorted_and_bucketed(case,
                                                          monkeypatch):
    """The scatter is told its indices are sorted in tile order: hold every
    build, padding included, to that, and every index to the matrix."""
    calls = []
    scatter = peel_loop._scatter_tiles

    def recording(eu, ev, n_real, shape, dtype):
        calls.append((np.asarray(eu), np.asarray(ev), n_real, shape))
        return scatter(eu, ev, n_real, shape, dtype)

    monkeypatch.setattr(peel_loop, "_scatter_tiles", recording)
    g, members, plan = CASES[case]
    stats = RunStats()
    dg = DeviceGraph(g, members, ReceiptConfig(kernel_blocks=BLOCKS),
                     plan=plan, stats=stats)
    ((eu, ev, n_real, shape),) = calls
    assert eu.dtype == ev.dtype == np.int32
    assert shape == (dg.rows_pad, dg.cols_pad)
    n = len(eu)
    assert n >= 1024 and n & (n - 1) == 0
    if plan is not None:
        assert n == plan.quantize_dim("dgm_edges", bucket(n_real, 1024))
    # every index lies inside the matrix: the TPU lowering marks an
    # out-of-bounds index -1, which breaks the sorted promise
    assert (eu >= 0).all() and (eu < dg.rows_pad).all()
    assert (ev >= 0).all() and (ev < dg.cols_pad).all()
    # sorted where the scatter writes them: tile after tile, row-major
    # inside a tile
    tr, tc = peel_loop._tile(shape)
    key = ((eu.astype(np.int64) // tr * (dg.cols_pad // tc) + ev // tc)
           * tr * tc + eu % tr * tc + ev % tc)
    assert (np.diff(key) >= 0).all()
    # the first n_real are the matrix's ones, once each; the padding is
    # its last cell
    a = np.asarray(dg.a)
    assert n_real == int(a.sum()) and len(np.unique(key[:n_real])) == n_real
    assert a[eu[:n_real], ev[:n_real]].all()
    assert (eu[n_real:] == dg.rows_pad - 1).all()
    assert (ev[n_real:] == dg.cols_pad - 1).all()
    if case == "tile-order":
        assert (tr, tc) == (8, 128) and (np.diff(eu) < 0).any()
    # the edges, dv0, w, row_ext and kmax: O(m + rows + cols) bytes
    assert dg.upload_bytes == stats.cd_graph_upload_bytes == (
        8 * n + 4 * dg.cols_pad + 4 * dg.rows_pad + 4 * dg.rows_pad
        + 4 * dg.rows_pad // BLOCKS[0])
