"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (comment lines start '#').

    table3_time     exec time: BUP vs ParB-emulation vs RECEIPT   (Table 3 t)
    table3_wedges   wedges traversed                              (Table 3 ∧)
    table3_sync     synchronization rounds rho                    (Table 3 ρ)
    fig5_psweep     RECEIPT time vs P                             (Fig 5)
    fig67_ablation  HUC/DGM ablations (RECEIPT--/-/full)          (Figs 6-7)
    fig89_breakup   wedge & time breakup per phase                (Figs 8-9)
    kernel_bench    butterfly kernel: dense blocked vs segment
"""
from __future__ import annotations

import sys
import time
from typing import Callable, Dict

import numpy as np

sys.path.insert(0, "src")

from repro.core.peeling import bup_oracle, parb_metrics
from repro.core.receipt import ReceiptConfig, parb_tip_decompose, tip_decompose

from .datasets import DATASETS

BLOCKS = (8, 8, 8)


def _cfg(**kw):
    base = dict(num_partitions=24, kernel_blocks=BLOCKS, backend="xla")
    base.update(kw)
    return ReceiptConfig(**base)


def _time(fn, *args, repeat=1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return dt, out


_ORACLE_CACHE: Dict[str, tuple] = {}


def _oracle(name, g):
    if name not in _ORACLE_CACHE:
        dt_b, (tb, mb) = _time(bup_oracle, g)
        dt_p, (tp, mp) = _time(parb_metrics, g)
        _ORACLE_CACHE[name] = (dt_b, tb, mb, dt_p, tp, mp)
    return _ORACLE_CACHE[name]


def table3_time(rows):
    """Wall time: RECEIPT vs ParB on the SAME engine/kernels (the only
    difference is the peel schedule => sync rounds), plus the numpy BUP
    oracle as a host reference point.  First call per config warms the
    jit caches and is not timed (the paper times steady-state too)."""
    for name, make in DATASETS.items():
        g = make()
        dt_b, tb, mb, dt_p, tp, mp = _oracle(name, g)
        tip_decompose(g, _cfg())                      # warm-up (compile)
        dt_r, (tr, st) = _time(tip_decompose, g, _cfg())
        parb_tip_decompose(g, _cfg())                 # warm-up (compile)
        dt_pe, (tpe, st_p) = _time(parb_tip_decompose, g, _cfg())
        assert (tr == tb).all(), f"{name}: RECEIPT != BUP"
        assert (tpe == tb).all(), f"{name}: ParB engine != BUP"
        rows.append((f"table3_time/bup_oracle/{name}", dt_b * 1e6, "host numpy"))
        rows.append((
            f"table3_time/parb_engine/{name}", dt_pe * 1e6,
            f"rho={st_p.rho_cd}",
        ))
        rows.append((
            f"table3_time/receipt/{name}", dt_r * 1e6,
            f"rho={st.rho_cd} speedup_vs_parb={dt_pe/dt_r:.2f}x",
        ))


def table3_wedges(rows):
    for name, make in DATASETS.items():
        g = make()
        _, tb, mb, _, _, _ = _oracle(name, g)
        _, (tr, st) = _time(tip_decompose, g, _cfg())
        bup_total = mb.wedges_static + st.wedges_pvbcnt  # BUP also counts
        rows.append((
            f"table3_wedges/{name}", 0.0,
            f"bup={bup_total} receipt={st.wedges_total} "
            f"reduction={bup_total/max(st.wedges_total,1):.2f}x "
            f"pv={st.wedges_pvbcnt} cd={st.wedges_cd} fd={st.wedges_fd}",
        ))


def table3_sync(rows):
    for name, make in DATASETS.items():
        g = make()
        _, tb, mb, _, _, mp = _oracle(name, g)
        _, (tr, st) = _time(tip_decompose, g, _cfg())
        rows.append((
            f"table3_sync/{name}", 0.0,
            f"parb_rho={mp.rounds} receipt_rho={st.rho_cd} "
            f"reduction={mp.rounds/max(st.rho_cd,1):.1f}x",
        ))


def fig5_psweep(rows):
    g = DATASETS["itu_like"]()
    for p in (4, 12, 24, 48, 96):
        tip_decompose(g, _cfg(num_partitions=p))      # warm-up (compile)
        dt, (tr, st) = _time(tip_decompose, g, _cfg(num_partitions=p))
        rows.append((
            f"fig5_psweep/P={p}", dt * 1e6,
            f"subsets={st.num_subsets} rho={st.rho_cd} wedges={st.wedges_total}",
        ))


def fig67_ablation(rows):
    for name in ("tru_like", "itu_like"):
        g = DATASETS[name]()
        variants = {
            "receipt--": _cfg(use_huc=False, use_dgm=False),
            "receipt-": _cfg(use_huc=True, use_dgm=False),
            "receipt": _cfg(use_huc=True, use_dgm=True),
        }
        base = None
        for vn, cfg in variants.items():
            tip_decompose(g, cfg)                     # warm-up (compile)
            dt, (tr, st) = _time(tip_decompose, g, cfg)
            base = base or st.wedges_total
            rows.append((
                f"fig67_ablation/{name}/{vn}", dt * 1e6,
                f"wedges={st.wedges_total} norm={st.wedges_total/base:.3f} "
                f"huc={st.huc_recounts} dgm={st.dgm_compactions}",
            ))


def fig89_breakup(rows):
    for name, make in DATASETS.items():
        g = make()
        _, (tr, st) = _time(tip_decompose, g, _cfg())
        tot_w = max(st.wedges_total, 1)
        tot_t = max(st.time_count + st.time_cd + st.time_fd, 1e-9)
        rows.append((
            f"fig89_breakup/{name}", 0.0,
            f"wedge%: pv={100*st.wedges_pvbcnt/tot_w:.1f} "
            f"cd={100*st.wedges_cd/tot_w:.1f} fd={100*st.wedges_fd/tot_w:.1f} | "
            f"time%: cnt={100*st.time_count/tot_t:.1f} "
            f"cd={100*st.time_cd/tot_t:.1f} fd={100*st.time_fd/tot_t:.1f}",
        ))


def kernel_bench(rows):
    import jax
    import jax.numpy as jnp

    from repro.core.counting import (
        butterfly_counts_dense,
        butterfly_counts_segment,
        wedge_pair_table,
    )
    from repro.core.graph import powerlaw_bipartite

    g = powerlaw_bipartite(2048, 1024, 30000, seed=11)
    a = jnp.asarray(g.dense())
    fn = jax.jit(lambda a: butterfly_counts_dense(a, backend="xla"))
    fn(a).block_until_ready()
    dt, out = _time(lambda: fn(a).block_until_ready(), repeat=5)
    flops = 2.0 * a.shape[0] ** 2 * a.shape[1]
    rows.append((
        "kernel_bench/dense_xla", dt * 1e6,
        f"gflops={flops/dt/1e9:.1f} n_u={a.shape[0]} n_v={a.shape[1]}",
    ))

    us, ups = wedge_pair_table(g)
    usj, upsj = jnp.asarray(us), jnp.asarray(ups)
    seg = jax.jit(lambda u, v: butterfly_counts_segment(u, v, g.n_u))
    seg(usj, upsj).block_until_ready()
    dt2, _ = _time(lambda: seg(usj, upsj).block_until_ready(), repeat=5)
    rows.append((
        "kernel_bench/segment", dt2 * 1e6,
        f"wedges={len(us)} wedges_per_s={len(us)/dt2/1e6:.1f}M",
    ))

    # zero-stripe (block-sparse) opportunity after degree sorting: the
    # fraction of (BI x BK) A-tiles that are all-zero = the compute the
    # Pallas kernel's skip list removes (EXPERIMENTS.md kernel section)
    gs = g.relabel_by_degree()
    ad = gs.dense()
    for bi, bk in ((128, 512), (256, 512)):
        nu = (ad.shape[0] + bi - 1) // bi
        nv = (ad.shape[1] + bk - 1) // bk
        import numpy as _np

        pad = _np.zeros((nu * bi, nv * bk), ad.dtype)
        pad[: ad.shape[0], : ad.shape[1]] = ad
        tiles = pad.reshape(nu, bi, nv, bk).sum(axis=(1, 3))
        frac = float((tiles == 0).mean())
        rows.append((
            f"kernel_bench/tile_sparsity/{bi}x{bk}", 0.0,
            f"zero_tile_frac={frac:.3f} (degree-sorted powerlaw graph)",
        ))

    # staircase stripe-skip fraction for the block-sparse Pallas variant,
    # at production block sizes on a production-sparsity graph (the small
    # dense bench graph above has only 2 k-stripes, so skip=0 there)
    from repro.kernels.butterfly_sparse import column_extents

    g_sp = powerlaw_bipartite(16384, 16384, 120_000, seed=13).relabel_by_degree()
    ad_sp = g_sp.dense()
    for bi, bk in ((128, 512), (256, 512)):
        nu = ((ad_sp.shape[0] + bi - 1) // bi) * bi
        nv = ((ad_sp.shape[1] + bk - 1) // bk) * bk
        pad = _np.zeros((nu, nv), ad_sp.dtype)
        pad[: ad_sp.shape[0], : ad_sp.shape[1]] = ad_sp
        kmax = column_extents(pad, bi, bk)
        n_i, n_k = nu // bi, nv // bk
        skipped = sum(
            max(0, n_k - min(int(kmax[i]), int(kmax[j])))
            for i in range(n_i) for j in range(n_i)
        )
        rows.append((
            f"kernel_bench/stripe_skip/{bi}x{bk}", 0.0,
            f"skipped_stripe_frac={skipped/(n_i*n_i*n_k):.3f} "
            f"(16384x16384 m=102k powerlaw; MXU-step cut for "
            "butterfly_support_pallas_sparse)",
        ))


def wing_ext(rows):
    """Paper section 7 extension: wing decomposition (edge peeling)."""
    from repro.core.graph import random_bipartite
    from repro.core.wing import wing_bup_oracle, wing_decompose

    g = random_bipartite(24, 18, 0.3, seed=9)
    dt_o, (po, rounds) = _time(wing_bup_oracle, g)
    wing_decompose(g, num_partitions=6)               # warm-up (compile)
    dt_w, (pr, st) = _time(wing_decompose, g, num_partitions=6)
    assert (po == pr).all(), "wing != oracle"
    rows.append((
        "wing_ext/oracle", dt_o * 1e6, f"m={g.m} rounds={rounds}",
    ))
    rows.append((
        "wing_ext/receipt_cd_fd", dt_w * 1e6,
        f"rho_cd={st.rho_cd} subsets={st.num_subsets} "
        f"sync_reduction={rounds/max(st.rho_cd,1):.1f}x",
    ))


BENCHES = [
    table3_time, table3_wedges, table3_sync, fig5_psweep,
    fig67_ablation, fig89_breakup, kernel_bench,
    wing_ext,
]


def main() -> None:
    rows = []
    for bench in BENCHES:
        t0 = time.time()
        try:
            bench(rows)
        except Exception as e:  # keep the harness running
            import traceback

            traceback.print_exc()
            rows.append((f"{bench.__name__}/ERROR", 0.0, str(e)))
        print(f"# {bench.__name__} done in {time.time()-t0:.1f}s", flush=True)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    # append the dry-run roofline table when available (EXPERIMENTS.md §Roofline)
    import os

    if os.path.exists("results/dryrun.json"):
        print("\n# ===== roofline table (from results/dryrun.json) =====")
        from . import roofline_report

        roofline_report.main("results/dryrun.json")


if __name__ == "__main__":
    main()
