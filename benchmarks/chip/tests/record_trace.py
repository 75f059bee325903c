#!/usr/bin/env python3
"""Record the small TPU trace that tests/test_trace.py reduces.

    python3 benchmarks/chip/tests/record_trace.py <out_dir>

On the chip: one Pallas kernel call (``butterfly_support_pallas``) and a
jnp product inside the benchmark's window span, with a host span around
an idle sleep between them, traced with the benchmark's own settings.
Copy the ``.xplane.pb`` it writes to ``tests/data/small.xplane.pb``.
"""
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[3]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import trace  # noqa: E402
from repro.kernels import ops  # noqa: E402


def main(out_dir: str) -> None:
    a = (jax.random.uniform(jax.random.PRNGKey(0), (512, 512)) < 0.1
         ).astype(jnp.float32)
    s = jnp.ones((512,), jnp.float32)
    product = jax.jit(lambda x: (x @ x.T).sum())
    jax.block_until_ready((ops.butterfly_support(a, s), product(a)))
    with trace.capture(out_dir):
        time.sleep(0.02)
        jax.block_until_ready(ops.butterfly_support(a, s))
        with trace.span("sleep"):
            time.sleep(0.05)
        jax.block_until_ready(product(a))
        time.sleep(0.02)
    print(trace.find_xplane(out_dir))


if __name__ == "__main__":
    main(sys.argv[1])
