"""Fused butterfly-support Pallas TPU kernel.

Computes   out[i] = sum_{j : ids_b[j] != ids_a[i]} s[j] * C((A B^T)[i, j], 2)

in one pass: a blocked wedge matmul (MXU), the choose-2 nonlinearity and the
masked row reduction (VPU) are fused so the |I| x |J| wedge tile matrix never
leaves VMEM.  This is the wedge-traversal hot loop of RECEIPT — per-vertex
counting, batched CD peel updates and HUC recounts are all this op
(see DESIGN.md section 2.1):

    counting / recount:  A = B = biadjacency,  s = alive mask
    CD peel update:      A = biadjacency, B = gathered peel rows A[S],
                         s = validity of gathered rows (padding mask)

``ids_a`` / ``ids_b`` carry the *global* U ids of each row so self-pairs
(u, u) are excluded even when B holds gathered copies of A rows.

Grid layout
-----------
    grid = (nI, nJ, nK)        I: output row tiles     (parallel)
                               J: mask/peel row tiles  (reduction)
                               K: V contraction tiles  (reduction)

For fixed (i, j) the wedge tile W_ij = A_i B_j^T accumulates over k in a
VMEM scratch; at k == nK-1 the epilogue applies C(W, 2), the row mask s_j
and the self-pair mask, then row-reduces into out_i.  out_i stays resident
in VMEM across all (j, k) steps of a fixed i (k fastest, then j), so HBM
traffic = read A/B tiles + one out write; the wedge matrix itself never
touches HBM.

Block sizes default to (128, 128, 512): MXU-aligned, ~0.7 MB of VMEM.

Exactness: W < 2^24 exact (f32 accumulation of 0/1 products; holds for
|V| < 2^24).  C(W,2) and the output accumulate in f32; integer-exactness
limits are asserted by callers and swept in tests (DESIGN.md section 8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "butterfly_kernel_body",
    "butterfly_support_pallas",
    "butterfly_update_pallas_batched",
]

DEFAULT_BLOCKS = (128, 128, 512)


def butterfly_kernel_body(
    a_ref,        # (BI, BK)  output-side rows
    b_ref,        # (BJ, BK)  mask-side rows (possibly gathered)
    s_ref,        # (1, BJ)   row mask tile
    ida_ref,      # (1, BI)   global U ids of output rows
    idb_ref,      # (1, BJ)   global U ids of mask rows
    out_ref,      # (1, BI)   output tile (accumulated across j, k)
    w_acc_ref,    # (BI, BJ)  VMEM scratch: wedge tile accumulator
    *,
    n_k: int,
):
    j, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _zero_wedge_acc():
        w_acc_ref[...] = jnp.zeros_like(w_acc_ref)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _zero_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    # ---- MXU: accumulate the wedge tile over the V contraction ---------
    w_acc_ref[...] += jax.lax.dot_general(
        a_ref[...],
        b_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # ---- VPU epilogue at the last contraction step ----------------------
    @pl.when(k == n_k - 1)
    def _epilogue():
        w = w_acc_ref[...]
        not_self = (
            ida_ref[0, :][:, None] != idb_ref[0, :][None, :]
        ).astype(w.dtype)
        b2 = w * (w - 1.0) * 0.5
        contrib = b2 * not_self * s_ref[0, :][None, :]
        out_ref[...] += jnp.sum(contrib, axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def butterfly_support_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    s: jnp.ndarray,
    ids_a: jnp.ndarray,
    ids_b: jnp.ndarray,
    *,
    blocks: tuple = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jnp.ndarray:
    """out[i] = sum_{j: ids_b[j] != ids_a[i]} s[j] * C((A B^T)[i, j], 2).

    a: (n_a, n_v) f32 0/1; b: (n_b, n_v) f32 0/1; s: (n_b,) mask;
    ids: int32 global row ids.  All dims must be pre-padded to blocks.
    """
    n_a, n_v = a.shape
    n_b = b.shape[0]
    bi, bj, bk = blocks
    if n_a % bi or n_b % bj or n_v % bk:
        raise ValueError(f"shapes {a.shape}/{b.shape} not padded to {blocks}")
    n_i, n_j, n_k = n_a // bi, n_b // bj, n_v // bk

    kernel = functools.partial(butterfly_kernel_body, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(n_i, n_j, n_k),
        in_specs=[
            pl.BlockSpec((bi, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bj, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, bj), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bi), lambda i, j, k: (0, i)),
            pl.BlockSpec((1, bj), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bi), lambda i, j, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_a), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        a.astype(jnp.float32),
        b.astype(jnp.float32),
        s.reshape(1, n_b).astype(jnp.float32),
        ids_a.reshape(1, n_a).astype(jnp.int32),
        ids_b.reshape(1, n_b).astype(jnp.int32),
    )
    return out[0]


# ---------------------------------------------------------------------- #
# grouped / batched entry point (FD level-peel stacks)
# ---------------------------------------------------------------------- #
def butterfly_batched_kernel_body(
    a_ref,        # (1, BI, BK)  output-side rows of one group
    b_ref,        # (1, BJ, BK)  mask-side rows of one group
    s_ref,        # (1, 1, BJ)   row mask tile
    ida_ref,      # (1, 1, BI)   local U ids of output rows
    idb_ref,      # (1, 1, BJ)   local U ids of mask rows
    out_ref,      # (1, 1, BI)   output tile
    w_acc_ref,    # (BI, BJ)     VMEM scratch: wedge tile accumulator
    *,
    n_k: int,
):
    """Group-batched variant of ``butterfly_kernel_body``: grid gains a
    leading group dimension (one independent FD subset per group slot), so
    a whole vmap stack of induced subgraphs is swept by ONE kernel launch.
    The per-group computation is identical to the single-graph body."""
    j, k = pl.program_id(2), pl.program_id(3)

    @pl.when(k == 0)
    def _zero_wedge_acc():
        w_acc_ref[...] = jnp.zeros_like(w_acc_ref)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _zero_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    w_acc_ref[...] += jax.lax.dot_general(
        a_ref[0],
        b_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        w = w_acc_ref[...]
        not_self = (
            ida_ref[0, 0, :][:, None] != idb_ref[0, 0, :][None, :]
        ).astype(w.dtype)
        b2 = w * (w - 1.0) * 0.5
        contrib = b2 * not_self * s_ref[0, 0, :][None, :]
        out_ref[...] += jnp.sum(contrib, axis=1)[None, None, :]


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def butterfly_update_pallas_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    s: jnp.ndarray,
    ids_a: jnp.ndarray,
    ids_b: jnp.ndarray,
    *,
    blocks: tuple = DEFAULT_BLOCKS,
    interpret: bool = False,
) -> jnp.ndarray:
    """out[g, i] = sum_{j: ids_b[g,j] != ids_a[g,i]} s[g,j] * C((A_g B_g^T)[i,j], 2).

    a: (G, n_a, n_v); b: (G, n_b, n_v); s: (G, n_b); ids: (G, n) int32
    LOCAL row ids within each group.  Row/col dims must be pre-padded to
    blocks; the group dim is unconstrained (block size 1).  One launch
    sweeps every stacked subset — the grouped entry point the FD
    level-peel runtime dispatches through.
    """
    g_n, n_a, n_v = a.shape
    n_b = b.shape[1]
    bi, bj, bk = blocks
    if n_a % bi or n_b % bj or n_v % bk:
        raise ValueError(f"shapes {a.shape}/{b.shape} not padded to {blocks}")
    n_i, n_j, n_k = n_a // bi, n_b // bj, n_v // bk

    kernel = functools.partial(butterfly_batched_kernel_body, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(g_n, n_i, n_j, n_k),
        in_specs=[
            pl.BlockSpec((1, bi, bk), lambda g, i, j, k: (g, i, k)),
            pl.BlockSpec((1, bj, bk), lambda g, i, j, k: (g, j, k)),
            pl.BlockSpec((1, 1, bj), lambda g, i, j, k: (g, 0, j)),
            pl.BlockSpec((1, 1, bi), lambda g, i, j, k: (g, 0, i)),
            pl.BlockSpec((1, 1, bj), lambda g, i, j, k: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bi), lambda g, i, j, k: (g, 0, i)),
        out_shape=jax.ShapeDtypeStruct((g_n, 1, n_a), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "arbitrary", "arbitrary",
            ),
        ),
        interpret=interpret,
    )(
        a.astype(jnp.float32),
        b.astype(jnp.float32),
        s.reshape(g_n, 1, n_b).astype(jnp.float32),
        ids_a.reshape(g_n, 1, n_a).astype(jnp.int32),
        ids_b.reshape(g_n, 1, n_b).astype(jnp.int32),
    )
    return out[:, 0, :]
