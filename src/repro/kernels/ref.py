"""Pure-jnp oracles for the butterfly kernels.

These are the ground-truth implementations every Pallas kernel is swept
against (tests/test_kernels.py).  They materialize the full |U| x |U| wedge
matrix, which is exactly what the fused kernel avoids.

Math (DESIGN.md section 2.1): with A the 0/1 biadjacency of G(U, V, E),

    W  = A A^T                  (pairwise wedge counts; invariant under
                                 peeling because V is never deleted)
    B2 = C(W, 2), zero diag     (pairwise shared butterflies)

    butterfly_support(A, s)[i] = sum_j s[j] * B2[i, j]

which covers (a) per-vertex counting  (s = alive),
             (b) batched peel updates (s = peel set indicator),
             (c) HUC recounts         (s = alive-after-peel).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["EXACT", "wedge_matrix", "shared_butterflies",
           "butterfly_support_ref"]

# f32 products with an operand that can exceed 256 (butterfly counts, B2
# rows, degrees) run at HIGHEST: a TPU's DEFAULT f32 matmul is one bf16
# pass, exact only for integers up to 256 (DESIGN.md §8).  0/1 x 0/1
# products stay at the default.
EXACT = jax.lax.Precision.HIGHEST


def wedge_matrix(a: jnp.ndarray) -> jnp.ndarray:
    """W = A A^T.  a: (n_u, n_v) 0/1 matrix."""
    return a @ a.T


def shared_butterflies(a: jnp.ndarray) -> jnp.ndarray:
    """B2[i, j] = C(W[i, j], 2) with a zeroed diagonal."""
    w = wedge_matrix(a)
    b2 = w * (w - 1) / 2
    n = a.shape[0]
    return b2 * (1 - jnp.eye(n, dtype=a.dtype))


def butterfly_support_ref(a: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """out[i] = sum_{j != i} s[j] * C(W[i, j], 2).

    a: (n_u, n_v) 0/1; s: (n_u,) 0/1 row-mask (the "peel set" / alive set).
    """
    b2 = shared_butterflies(a)
    return jnp.matmul(b2, s.astype(a.dtype), precision=EXACT)
