"""RECEIPT's own configuration (the paper's settings + our TPU engine).

The paper (section 5.1) uses P=150 partitions and 36 threads on a
dual-socket Xeon; the TPU engine's equivalents are below.  The dry-run
cells (configs/shapes.py RECEIPT_SHAPES) exercise the production-scale
distributed steps; `reduced_config` drives CPU benchmarks/tests.
"""
from ..core.receipt import ReceiptConfig

ARCH_ID = "receipt-tip"


def full_config() -> ReceiptConfig:
    # paper defaults, production kernel blocks (EXPERIMENTS.md kernel
    # section: (256, 256, 512) rides the v5e ridge point)
    return ReceiptConfig(
        num_partitions=150,
        kernel_blocks=(256, 256, 512),
        use_huc=True,
        use_dgm=True,
        degree_sort=True,
    )


def reduced_config() -> ReceiptConfig:
    return ReceiptConfig(
        num_partitions=24,
        kernel_blocks=(8, 8, 8),
        backend="xla",
    )
