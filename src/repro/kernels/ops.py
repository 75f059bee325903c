"""Dispatching wrappers for the butterfly kernels.

``butterfly_support(a, s)`` / ``butterfly_update(a, b, s, ids_a, ids_b)``
are THE hot ops of the framework: RECEIPT's per-vertex counting, CD batched
peel updates and HUC recounts are all these ops with different masks/rows.
The wrappers:

  * route to the Pallas kernel (TPU), the Pallas interpreter (CPU
    validation of the same kernel body), or the pure-jnp oracle
    (fast CPU execution path for benchmarks),
  * keep everything jittable (fixed shapes; padding is the caller's
    responsibility via the bucketing helpers in core/engine/peel_loop.py).

Backends (DESIGN.md section 2.1 routing table):
    "pallas"            pl.pallas_call, compiled (TPU target), dense tiles
    "pallas_sparse"     compiled block-sparse staircase kernel — skips
                        k-stripes beyond the scalar-prefetched column
                        extents (requires kmax_a/kmax_b metadata; falls
                        back to conservative full extents when absent)
    "interpret"         pl.pallas_call(interpret=True) -- executes the
                        dense kernel body via the interpreter (CPU checks)
    "interpret_sparse"  interpreter path of the block-sparse kernel
    "xla"               pure-jnp oracle (kernels/ref.py), whole-matrix
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .butterfly import (
    DEFAULT_BLOCKS,
    butterfly_support_pallas,
    butterfly_update_pallas_batched,
)
from .butterfly_sparse import (
    b2_stack_pallas_sparse,
    butterfly_update_pallas_sparse,
    butterfly_update_pallas_sparse_batched,
    row_extents_device,
)
from .butterfly_tiled import (
    butterfly_update_pallas_tiled,
    butterfly_update_tiled_xla,
)

__all__ = [
    "butterfly_support",
    "butterfly_update",
    "butterfly_update_batched",
    "butterfly_update_tiled",
    "b2_stack",
    "edge_support_all",
    "edge_support_delta",
    "vertex_support_edge_delta",
    "find_hi_device",
    "tighten_extents_device",
    "default_backend",
    "resolve_backend",
    "route_label",
    "fallback_backend",
    "fallback_chain",
    "KNOWN_BACKENDS",
    "SPARSE_BACKENDS",
]

SPARSE_BACKENDS = ("pallas_sparse", "interpret_sparse")
KNOWN_BACKENDS = ("pallas", "pallas_sparse", "interpret", "interpret_sparse",
                  "xla")

# kernel-route labels surfaced by the planning layer (repro.api): what a
# backend actually executes, for humans reading an ExecutionPlan
_ROUTE_LABELS = {
    "pallas": "pallas-dense (compiled blocked kernel)",
    "pallas_sparse": "pallas-sparse (compiled staircase stripe-skip)",
    "interpret": "interpret-dense (Pallas interpreter)",
    "interpret_sparse": "interpret-sparse (Pallas interpreter)",
    "xla": "xla-oracle (pure-jnp reference)",
}


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve_backend(backend: Optional[str]) -> str:
    """Validate + resolve a kernel backend name (None = platform default).

    Every dispatcher below routes through this, so a typo'd backend fails
    with an actionable error instead of silently falling through to the
    compiled pallas path (the pre-PR-5 behavior).
    """
    if backend is None:
        return default_backend()
    if backend not in KNOWN_BACKENDS:
        import difflib

        hints = difflib.get_close_matches(backend, KNOWN_BACKENDS, n=1)
        hint = f" (did you mean {hints[0]!r}?)" if hints else ""
        raise ValueError(
            f"unknown kernel backend {backend!r}{hint}; known backends: "
            f"{', '.join(KNOWN_BACKENDS)}")
    return backend


def route_label(backend: Optional[str]) -> str:
    """Human-readable kernel route of a backend (ExecutionPlan field)."""
    return _ROUTE_LABELS[resolve_backend(backend)]


# graceful-degradation routing (DESIGN.md §7): every backend's next stop
# when its launches fail — compiled kernel -> same kernel body under the
# interpreter -> the pure-jnp oracle.  All stops are exact (bit-identical
# in the f32 integer regime), so degrading trades speed, never results.
# On a TPU the interpreter stops are skipped: the interpreter is a CPU
# validation tool, and a compiled kernel that fails there must surface
# as a (warned) drop to the jnp oracle, not as a slow interpreted re-run.
_FALLBACK_NEXT = {
    "pallas": "interpret",
    "pallas_sparse": "interpret_sparse",
    "interpret": "xla",
    "interpret_sparse": "xla",
    "xla": None,
}
_INTERPRETERS = ("interpret", "interpret_sparse")


def fallback_backend(backend: Optional[str]) -> Optional[str]:
    """The next backend in the degradation chain (None = end of chain)."""
    b = _FALLBACK_NEXT[resolve_backend(backend)]
    if b in _INTERPRETERS and jax.default_backend() == "tpu":
        b = _FALLBACK_NEXT[b]
    return b


def fallback_chain(backend: Optional[str]) -> tuple:
    """The full degradation chain starting AT ``backend`` (inclusive):
    ``pallas -> interpret -> xla`` (``pallas -> xla`` on a TPU),
    ``interpret_sparse -> xla``, ...  The Executor walks this on
    ``KernelBackendError`` (DESIGN.md §7)."""
    b: Optional[str] = resolve_backend(backend)
    chain = []
    while b is not None:
        chain.append(b)
        b = fallback_backend(b)
    return tuple(chain)


@jax.jit
def find_hi_device(support, alive, w, tgt):
    """Adaptive range upper bound (Alg. 3 findHi) as a device reduction.

    The wedge-mass histogram over support values at exact (per-value)
    resolution: sort alive supports ascending, prefix-sum their residual
    wedge counts, and return ``s + 1`` for the smallest support ``s``
    whose cumulative wedge mass reaches ``tgt``.  When the target exceeds
    the remaining mass the result is ``max(alive support) + 1`` — the
    catch-all bound, which ``tgt = inf`` selects directly.

    Device twin of ``core/engine/cd.find_hi_np``: the whole-graph CD loop
    (``engine/peel_loop.device_cd_graph_loop``) calls it at every subset
    boundary so range determination costs no host sync (DESIGN.md §2.3).
    Prefix sums accumulate in f32 and are exact while the total residual
    wedge mass stays below 2**24; the host path prefix-sums in f64
    (DESIGN.md §8 lists the divergence).
    """
    f32 = jnp.float32
    sup = jnp.where(alive, support, jnp.inf).astype(f32)
    order = jnp.argsort(sup)
    ws = jnp.where(alive, w, 0.0).astype(f32)[order]
    cum = jnp.cumsum(ws)
    hit = cum >= tgt
    hi_hit = sup[order][jnp.argmax(hit)]
    hi_max = jnp.max(jnp.where(alive, support.astype(f32), -jnp.inf))
    return jnp.where(jnp.any(hit), hi_hit, hi_max) + 1.0


@functools.partial(jax.jit, static_argnames=("block_rows", "block_k"))
def tighten_extents_device(a, n_live_cols, *, block_rows, block_k):
    """Compaction-aware staircase extents, recomputed ON DEVICE.

    After the whole-graph CD loop compacts the residual graph at a subset
    boundary (dead rows zeroed, live-V columns gathered into a dense
    prefix of ``n_live_cols`` columns), every row's nonzeros sit inside
    the live prefix, so both the per-row extents and the row-tile extents
    the sparse kernels scalar-prefetch can be re-tightened without a host
    round trip.  The live-column count clamps the extents at
    ``ceil(n_live_cols / block_k)`` — the dead suffix is provably
    all-zero, so every kernel k-stripe beyond it is skipped exactly.

    Returns ``(row_ext, kmax)``: per-row extents ((n_rows,) int32, the
    B-side source for ``gathered_tile_extents``) and per-row-tile extents
    ((n_rows/block_rows,) int32, the scalar-prefetched A-side vector).
    """
    ext = row_extents_device(a, block_k)
    cap = ((n_live_cols + block_k - 1) // block_k).astype(jnp.int32)
    ext = jnp.minimum(ext, cap)
    kmax = ext.reshape(-1, block_rows).max(axis=1)
    return ext, kmax


def _update_ref(a, b, s, ids_a, ids_b):
    w = a @ b.T
    b2 = w * (w - 1.0) * 0.5
    not_self = (ids_a[:, None] != ids_b[None, :]).astype(a.dtype)
    return jnp.matmul(b2 * not_self, s.astype(a.dtype), precision=ref.EXACT)


def _full_extents(n_rows: int, block_rows: int, n_k: int) -> jnp.ndarray:
    """Conservative extents (no stripes skipped) — exact fallback when a
    sparse backend is selected but no staircase metadata is available."""
    return jnp.full((n_rows // block_rows,), n_k, jnp.int32)


@functools.partial(jax.jit, static_argnames=("backend", "blocks"))
def butterfly_update(
    a: jnp.ndarray,
    b: jnp.ndarray,
    s: jnp.ndarray,
    ids_a: jnp.ndarray,
    ids_b: jnp.ndarray,
    *,
    backend: Optional[str] = None,
    blocks: tuple = DEFAULT_BLOCKS,
    kmax_a: Optional[jnp.ndarray] = None,
    kmax_b: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """out[i] = sum_{j: ids_b[j] != ids_a[i]} s[j] * C((A B^T)[i, j], 2).

    The general (gathered peel set) form.  Shapes must already be padded
    to the kernel blocks for the pallas/interpret backends.  ``kmax_a`` /
    ``kmax_b`` are row-tile column extents ((n_a/bi,) / (n_b/bj,) int32)
    consumed only by the sparse backends.
    """
    backend = resolve_backend(backend)
    if backend == "xla":
        return _update_ref(a, b, s, ids_a, ids_b)
    if backend in SPARSE_BACKENDS:
        bi, bj, bk = blocks
        n_k = a.shape[1] // bk
        if kmax_a is None:
            kmax_a = _full_extents(a.shape[0], bi, n_k)
        if kmax_b is None:
            kmax_b = _full_extents(b.shape[0], bj, n_k)
        return butterfly_update_pallas_sparse(
            a, b, s, ids_a, ids_b, kmax_a, kmax_b,
            blocks=blocks, interpret=(backend == "interpret_sparse"),
        )
    return butterfly_support_pallas(
        a, b, s, ids_a, ids_b, blocks=blocks, interpret=(backend == "interpret")
    )


def _update_ref_batched(a, b, s, ids_a, ids_b):
    w = jnp.einsum("gic,gjc->gij", a, b)
    b2 = w * (w - 1.0) * 0.5
    not_self = (ids_a[:, :, None] != ids_b[:, None, :]).astype(a.dtype)
    return jnp.einsum("gij,gj->gi", b2 * not_self, s.astype(a.dtype),
                      precision=ref.EXACT)


@functools.partial(jax.jit, static_argnames=("backend", "blocks"))
def butterfly_update_batched(
    a: jnp.ndarray,
    b: jnp.ndarray,
    s: jnp.ndarray,
    ids_a: jnp.ndarray,
    ids_b: jnp.ndarray,
    *,
    backend: Optional[str] = None,
    blocks: tuple = DEFAULT_BLOCKS,
    kmax_a: Optional[jnp.ndarray] = None,
    kmax_b: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Grouped/batched butterfly update over a stack of independent
    subgraphs (the FD level-peel hot op):

        out[g, i] = sum_{j: ids_b[g,j] != ids_a[g,i]} s[g,j]
                    * C((A_g B_g^T)[i, j], 2)

    a: (G, n_a, n_v); b: (G, n_b, n_v); s: (G, n_b); ids (G, n) LOCAL
    row ids.  ``kmax_a`` / ``kmax_b`` are per-group row-tile column
    extents ((G, n_a/bi) / (G, n_b/bj) int32) consumed only by the sparse
    backends — each stacked subset carries its own staircase.
    """
    backend = resolve_backend(backend)
    if backend == "xla":
        return _update_ref_batched(a, b, s, ids_a, ids_b)
    if backend in SPARSE_BACKENDS:
        bi, bj, bk = blocks
        n_k = a.shape[2] // bk
        g_n = a.shape[0]
        if kmax_a is None:
            kmax_a = jnp.full((g_n, a.shape[1] // bi), n_k, jnp.int32)
        if kmax_b is None:
            kmax_b = jnp.full((g_n, b.shape[1] // bj), n_k, jnp.int32)
        return butterfly_update_pallas_sparse_batched(
            a, b, s, ids_a, ids_b, kmax_a, kmax_b,
            blocks=blocks, interpret=(backend == "interpret_sparse"),
        )
    return butterfly_update_pallas_batched(
        a, b, s, ids_a, ids_b, blocks=blocks,
        interpret=(backend == "interpret"),
    )


def butterfly_update_tiled(
    tile_data: jnp.ndarray,
    srow: jnp.ndarray,
    scol: jnp.ndarray,
    sptr: jnp.ndarray,
    pos: jnp.ndarray,
    slot_live: jnp.ndarray,
    s: jnp.ndarray,
    *,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """Mask-form butterfly update over a nonzero-tile list
    (``core.graph.TiledGraph`` arrays):

        out[x] = sum_{y != x} s[y] * C((A A^T)[x, y], 2)

    Backend routing mirrors the dense ops: pallas/pallas_sparse run the
    compiled tiled kernel (the tiled form subsumes the staircase skip —
    a trailing zero stripe simply has no slot), interpret variants run
    the same kernel body under the interpreter, and xla runs the
    streaming jnp oracle that never materializes the dense biadjacency.
    """
    backend = resolve_backend(backend)
    if backend == "xla":
        return butterfly_update_tiled_xla(
            tile_data, srow, scol, sptr, pos, slot_live, s)
    return butterfly_update_pallas_tiled(
        tile_data, srow, scol, sptr, pos, slot_live, s,
        interpret=backend in ("interpret", "interpret_sparse"))


def _b2_stack_ref(a: jnp.ndarray) -> jnp.ndarray:
    w = jnp.einsum("gmc,gnc->gmn", a, a)
    b2 = w * (w - 1.0) * 0.5
    eye = jnp.eye(a.shape[1], dtype=a.dtype)
    return b2 * (1.0 - eye)[None]


@functools.partial(jax.jit, static_argnames=("backend", "blocks"))
def b2_stack(
    a: jnp.ndarray,
    *,
    backend: Optional[str] = None,
    blocks: tuple = DEFAULT_BLOCKS,
) -> jnp.ndarray:
    """Pairwise-butterfly stack ``out[g, x, y] = C((A_g A_g^T)[x, y], 2)``
    with the diagonal zeroed — the ``fd_update_mode="b2"`` precompute.

    On the Pallas backends the einsum + C(w, 2) + eye-mask pipeline is
    fused into one staircase-skipping kernel (extents derived on device
    from the rows themselves, so the skip needs no host metadata); the
    xla backend keeps the reference einsum.  Bit-identical across
    backends in the f32 integer regime.
    """
    backend = resolve_backend(backend)
    if backend == "xla":
        return _b2_stack_ref(a)
    bi, bj, bk = blocks
    ext = jax.vmap(lambda x: row_extents_device(x, bk))(a)
    kmax = ext.reshape(a.shape[0], -1, bi).max(axis=2)
    return b2_stack_pallas_sparse(
        a, kmax, blocks=blocks,
        interpret=backend in ("interpret", "interpret_sparse"))


@functools.partial(jax.jit, static_argnames=("backend", "blocks"))
def butterfly_support(
    a: jnp.ndarray,
    s: jnp.ndarray,
    *,
    backend: Optional[str] = None,
    blocks: tuple = DEFAULT_BLOCKS,
    kmax: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """out[i] = sum_{j != i} s[j] * C((A A^T)[i, j], 2)  (counting form).

    a: (n_u, n_v) 0/1 float array; s: (n_u,) mask.  For the pallas and
    interpret backends, shapes must be padded to the kernel blocks.
    """
    backend = resolve_backend(backend)
    if backend == "xla":
        return ref.butterfly_support_ref(a, s)
    n_u = a.shape[0]
    ids = jnp.arange(n_u, dtype=jnp.int32)
    return butterfly_update(
        a, a, s, ids, ids, backend=backend, blocks=blocks,
        kmax_a=kmax, kmax_b=kmax,
    )


# ---------------------------------------------------------------------- #
# edge-axis entry points (wing / bitruss peeling, DESIGN.md section 10)
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("backend", "blocks"))
def edge_support_all(
    a: jnp.ndarray,
    eu: jnp.ndarray,
    ev: jnp.ndarray,
    *,
    backend: Optional[str] = None,
    blocks: tuple = DEFAULT_BLOCKS,
) -> jnp.ndarray:
    """Per-edge butterfly supports of a residual graph, closed form:

        b(u, v) = [A (A^T A)](u, v) - d_u(u) - d_v(v) + 1   (alive edges)

    gathered at the edge slots ``(eu, ev)``; absent edges (peeled, or
    padding slots) report 0.  ``a`` may carry arbitrary leading batch
    dims — (R, C) for the single-graph CD recount, (G, R, C) for the
    stacked wing-FD level loop — with ``eu``/``ev`` shaped to match
    (``(E,)`` or ``(G, E)``).

    This is the edge axis's ALWAYS-AVAILABLE recount path (the HUC
    alternative the paper notes matters MORE for edge peeling): two
    matmuls, batched-exact, no double-delete bookkeeping.  Every backend
    shares the same jnp contraction — XLA already lowers the matmul pair
    onto the MXU optimally, so unlike the wedge kernels there is no
    custom Pallas body to route to; ``backend``/``blocks`` are accepted
    for signature parity with the vertex-axis ops (and validated).
    """
    resolve_backend(backend)
    at = jnp.swapaxes(a, -1, -2)
    m3 = jnp.matmul(a, at @ a, precision=ref.EXACT)
    du = jnp.sum(a, axis=-1)
    dvv = jnp.sum(a, axis=-2)
    if a.ndim == 2:
        b = m3[eu, ev] - du[eu] - dvv[ev] + 1.0
        return b * a[eu, ev]
    g = jnp.arange(a.shape[0])[:, None]
    b = m3[g, eu, ev] - du[g, eu] - dvv[g, ev] + 1.0
    return b * a[g, eu, ev]


def _edge_peel_update(a, u, v):
    """Support-delta matrix of peeling ONE edge (u, v) from ``a`` — the
    masked-matvec / rank-1 decomposition of the butterflies through
    (u, v) (same algebra as ``core/wing.py``'s sequential FD oracle):

        (u, v')  loses one butterfly per wedge partner u'  -> (A^T c_v) * r_u
        (u', v)  loses one per partner v'                  -> (A r_u) * c_v
        (u', v') loses exactly one per butterfly           -> outer(c_v, r_u) * A

    with the u'=u / v'=v self-wedge terms subtracted (those are wedges,
    not butterflies) and the peeled cell itself zeroed.
    """
    row_u = a[u]
    col_v = a[:, v]
    d_uv = jnp.zeros_like(a)
    d_uv = d_uv.at[u].add((jnp.swapaxes(a, -1, -2) @ col_v) * row_u)
    d_uv = d_uv.at[:, v].add((a @ row_u) * col_v)
    d_uv = d_uv + jnp.outer(col_v, row_u) * a
    d_uv = d_uv.at[u, v].set(0.0)
    d_uv = d_uv.at[u].add(-(row_u * row_u))
    d_uv = d_uv.at[:, v].add(-(col_v * col_v))
    d_uv = d_uv.at[u, :].add(-(col_v[u] * row_u * a[u]))
    d_uv = d_uv.at[:, v].add(-(row_u[v] * col_v * a[:, v]))
    d_uv = d_uv.at[u, v].set(0.0)
    return d_uv


@functools.partial(jax.jit, static_argnames=("backend", "blocks"))
def edge_support_delta(
    a: jnp.ndarray,
    eu: jnp.ndarray,
    ev: jnp.ndarray,
    rows: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    backend: Optional[str] = None,
    blocks: tuple = DEFAULT_BLOCKS,
) -> jnp.ndarray:
    """Incremental edge-axis peel update: total support decrease of every
    edge slot after removing the gathered edge set, SEQUENTIALLY exact.

    ``rows`` (W,) int32 holds edge-slot indices into ``eu``/``ev``;
    ``valid`` (W,) bool masks the real entries.  The batched edge-peel
    double-delete conflict the paper flags ("only one of the peeled
    edges should update the support") is dissolved by composition: a
    ``fori_loop`` applies each edge's masked-matvec/rank-1 delta against
    the MATRIX AS ALREADY PEELED by its predecessors, so the summed
    delta equals before-minus-after of the closed-form recount exactly
    — bit-identical to ``edge_support_all`` on the residual (the
    equivalence the differential wing suite pins).  ``backend``/
    ``blocks`` are accepted for signature parity (validated; the deltas
    are pure-jnp on every backend).

    Returns ``delta`` shaped like ``eu`` (per edge slot, >= 0).
    """
    resolve_backend(backend)

    def body(i, carry):
        a_cur, acc = carry
        e = rows[i]
        on = valid[i]
        u, v = eu[e], ev[e]
        d = _edge_peel_update(a_cur, u, v)
        d = jnp.where(on, d, jnp.zeros_like(d))
        a_next = jnp.where(on, a_cur.at[u, v].set(0.0), a_cur)
        return a_next, acc + d

    _, dmat = jax.lax.fori_loop(
        0, rows.shape[0], body, (a, jnp.zeros_like(a)))
    return dmat[eu, ev]


@functools.partial(jax.jit, static_argnames=("backend", "blocks"))
def vertex_support_edge_delta(
    a: jnp.ndarray,
    mu: jnp.ndarray,
    mv: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    backend: Optional[str] = None,
    blocks: tuple = DEFAULT_BLOCKS,
) -> jnp.ndarray:
    """Incremental VERTEX-axis edge-mutation update: total butterfly-
    support decrease of every U row after removing the edge set
    ``(mu[i], mv[i])`` from ``a``, SEQUENTIALLY exact (the tip-number
    analogue of ``edge_support_delta`` — the maintenance op of the
    serving layer's incremental refresh, DESIGN.md §11).

    Removing one present edge (u, v) changes only the wedge counts
    ``W[u, w]`` (``W = A Aᵀ``), each by ``a[w, v]``, so the closed-form
    per-row delta is one masked matvec:

        delta(w != u) = a[w, v] * (W[u, w] - 1)
        delta(u)      = sum_{w != u} delta(w)    (= the edge's support)

    A ``fori_loop`` composes the per-edge deltas against the matrix AS
    ALREADY PEELED by the predecessors, so the summed delta equals
    before-minus-after of the counting kernel exactly (f32 integer
    regime, DESIGN.md §8) — run it on the union graph with ``rows`` =
    the inserted set to get per-vertex GAINS, with ``rows`` = the
    deleted set to get per-vertex LOSSES.  ``valid`` (same shape as
    ``mu``) masks padding entries, so mutation batches bucket to stable
    shapes.  Slots naming an absent cell contribute zero (the delta is
    gated on ``a[u, v]``).  ``backend``/``blocks`` are accepted for
    signature parity (validated; the deltas are pure-jnp everywhere).

    Returns ``delta`` (n_u,) float, >= 0.
    """
    resolve_backend(backend)

    def body(i, carry):
        a_cur, acc = carry
        on = valid[i]
        u, v = mu[i], mv[i]
        wvec = a_cur @ a_cur[u]                   # W[u, :] (edge present)
        c = a_cur[:, v] * (wvec - 1.0)
        c = c.at[u].set(0.0)
        c = c.at[u].set(jnp.sum(c))
        c = c * a_cur[u, v]                       # absent cell -> no-op
        c = jnp.where(on, c, jnp.zeros_like(c))
        a_next = jnp.where(on, a_cur.at[u, v].set(0.0), a_cur)
        return a_next, acc + c

    _, delta = jax.lax.fori_loop(
        0, mu.shape[0], body, (a, jnp.zeros(a.shape[0], a.dtype)))
    return delta
