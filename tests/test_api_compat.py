"""Deprecation path (PR 5 satellite): every name `core/receipt.py` ever
exported still imports and produces BIT-IDENTICAL tip numbers through
the compatibility wrappers over the `repro.api` service layer."""
import importlib

import numpy as np
import pytest

from repro.core.graph import BipartiteGraph
from repro.core.peeling import bup_oracle

from conftest import GRAPH_CASES

SMALL_BLOCKS = (8, 8, 8)

# the full historical surface: __all__ plus the pre-split private
# aliases downstream forks/notebooks reached into
RECEIPT_EXPORTS = [
    "ReceiptConfig", "RunStats", "tip_decompose", "receipt_cd",
    "receipt_fd", "parb_tip_decompose", "cd_checkpoint_state",
    "DeviceGraph", "device_peel_loop", "device_cd_graph_loop",
    "batched_level_loop", "host_sweep", "bucket", "find_hi_np",
    "_DeviceGraph", "_cd_device_loop", "_host_sweep", "_bucket",
    "_find_hi_np", "_support_all", "_support_delta", "_sweep_info",
    "_residual_dv", "_apply_delta",
]


def test_every_receipt_export_still_imports():
    mod = importlib.import_module("repro.core.receipt")
    missing = [n for n in RECEIPT_EXPORTS if not hasattr(mod, n)]
    assert not missing, f"compat facade lost exports: {missing}"
    for n in mod.__all__:
        assert hasattr(mod, n), n


def test_tip_decompose_wrapper_bit_identical_to_engine():
    """The compat wrapper routes through repro.api; theta AND the run
    counters must match a direct engine call exactly."""
    from repro.core.engine import tip_decompose as engine_td
    from repro.core.receipt import ReceiptConfig, tip_decompose

    for case in ("powerlaw", "vhub", "fig1"):
        g = GRAPH_CASES[case]()
        cfg = ReceiptConfig(num_partitions=6, kernel_blocks=SMALL_BLOCKS,
                            backend="xla")
        t_wrap, s_wrap = tip_decompose(g, cfg)
        t_eng, s_eng = engine_td(g, cfg)
        np.testing.assert_array_equal(t_wrap, t_eng)
        tb, _ = bup_oracle(g)
        np.testing.assert_array_equal(t_wrap, tb)
        assert s_wrap.rho_cd == s_eng.rho_cd
        assert s_wrap.wedges_cd == s_eng.wedges_cd
        assert s_wrap.rho_fd == s_eng.rho_fd
        assert s_wrap.host_round_trips == s_eng.host_round_trips
        assert s_wrap.num_subsets == s_eng.num_subsets


def test_tip_decompose_wrapper_preserves_side_and_kwargs():
    from repro.core.receipt import ReceiptConfig, tip_decompose

    g = GRAPH_CASES["powerlaw"]()
    cfg = ReceiptConfig(num_partitions=6, kernel_blocks=SMALL_BLOCKS,
                        backend="xla")
    tv, _ = tip_decompose(g, cfg, side="V")
    tb, _ = bup_oracle(g.transposed())
    np.testing.assert_array_equal(tv, tb)
    with pytest.raises(ValueError, match="side"):
        tip_decompose(g, cfg, side="W")


def test_phase_entry_points_unchanged():
    """receipt_cd/receipt_fd keep their phase-level contract (the
    service layer drives these same functions)."""
    from repro.core.receipt import (
        ReceiptConfig,
        RunStats,
        receipt_cd,
        receipt_fd,
    )

    g = GRAPH_CASES["er_small"]()
    cfg = ReceiptConfig(num_partitions=4, kernel_blocks=SMALL_BLOCKS,
                        backend="xla")
    stats = RunStats()
    sid, isup, bounds, _ = receipt_cd(g, cfg, stats)
    th = receipt_fd(g, sid, isup, bounds, cfg, stats)
    tb, _ = bup_oracle(g)
    np.testing.assert_array_equal(np.round(th).astype(np.int64), tb)


def test_parb_wrapper_unchanged():
    from repro.core.receipt import ReceiptConfig, parb_tip_decompose

    g = GRAPH_CASES["vhub"]()
    tb, _ = bup_oracle(g)
    tp, _ = parb_tip_decompose(
        g, ReceiptConfig(kernel_blocks=SMALL_BLOCKS, backend="xla"))
    np.testing.assert_array_equal(tp, tb)


def test_legacy_ab_configs_still_run():
    """Configurations the strict EngineConfig rejects must keep running
    through the legacy surface (the dgm-off A/B suite depends on it)."""
    from repro.core.receipt import ReceiptConfig, tip_decompose

    g = GRAPH_CASES["er_small"]()
    tb, _ = bup_oracle(g)
    t, stats = tip_decompose(g, ReceiptConfig(
        num_partitions=4, kernel_blocks=SMALL_BLOCKS, backend="xla",
        cd_dispatch="graph", use_dgm=False))
    np.testing.assert_array_equal(t, tb)
    assert stats.dgm_device_compactions == 0
