"""Tiled-sparse butterfly kernels: nonzero-block iteration.

The staircase kernels in ``butterfly_sparse.py`` skip TRAILING zero
k-stripes of each row tile — the degenerate dense-blocks case of block
sparsity (exact only because degree sort pushes nonzeros left).  This
module generalizes them to a true blocked-sparse representation
(``core.graph.TiledGraph``): the biadjacency is stored as a CSR list of
NONZERO ``[block x block_k]`` tiles, and the kernels iterate the slot
list instead of the dense tile grid, so both memory and wedge-kernel
work scale with the number of occupied tiles rather than
``rows_pad * cols_pad``.

Kernel geometry.  The grid is ``(active B bands, n_slots)`` — outer
index ``jj`` picks the ``jj``-th B row-band that carries ``s`` mass
(``jlist``), inner index ``t`` walks the tile slots in CSR order.
Scalar-prefetched maps drive the data movement exactly the way
``gathered_tile_extents`` drives the staircase kernel:

* ``srow[t]`` / ``sptr`` give each slot's row-band and the band
  boundaries, so the wedge accumulator is zeroed at a band's first slot
  and flushed (B2 epilogue) at its last — every band owns >= 1 slot by
  construction, so the lifecycle always fires.  The flush adds into an
  output that stays resident in VMEM for the whole grid: each A band is
  revisited once per B band, so the output cannot be a per-band block;
* the A tile is ``tile_data[t]``; the B tile is
  ``tile_data[pos[j, scol[t]]]``, a scalar-prefetch GATHER in the
  BlockSpec index map (clamped to 0 when absent; the kernel masks the
  contribution with ``pl.when``);
* ``slot_live`` is the tile-list regather: the DGM analogue for the
  tiled form.  Dead rows/columns are zeroed in ``tile_data`` between
  sweeps (``regather_tiles`` — exact by the same argument as dense DGM
  column compaction: a column with < 2 alive neighbors completes no
  wedge between alive vertices), and slots that became all-zero are
  skipped entirely.

The update form is the MASK form (B = A, ``s`` = peel mask over rows):
``out[x] = sum_{y != x} s[y] * C((A A^T)[x, y], 2)`` — with ``s`` = the
alive mask this is per-vertex butterfly counting, with ``s`` = a peel
mask it is the level-peel support delta.  A jnp streaming oracle
(``butterfly_update_tiled_xla``) computes the identical quantity one
row-band at a time without ever materializing the dense biadjacency,
giving the tiled path the same pallas/interpret/xla backend triangle as
the dense kernels; all three are bit-identical in the f32 integer
regime (counts < 2^24).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# fast-path width of the xla oracle's gathered-row pass: sweeps whose
# s mask touches at most this many rows (the common peel case) pay one
# vectorized slot-list pass over exactly those W columns instead of
# walking all n_rt band columns
_PEEL_ROW_WIDTH = 16

# on-chip memory of one TPU v5e core that the compiled kernel's tables
# fill; both grow with the graph (``tiled_kernel_bytes``).  The reserves
# cover the compiler's own use: compiles for a described v5e succeed at
# the admitted edges (tests/test_tpu_compile.py) and failed 2% past the
# VMEM edge and 12% past the SMEM one
SMEM_BYTES = 1 << 20          # scalar memory: the prefetched index tables
VMEM_BYTES = 16 << 20         # default scoped VMEM: output + tile buffers
_SMEM_RESERVE = 16 << 10
_VMEM_RESERVE = 256 << 10

__all__ = [
    "SMEM_BYTES",
    "VMEM_BYTES",
    "butterfly_update_pallas_tiled",
    "butterfly_update_tiled_xla",
    "colsum_tiled",
    "masked_colsum_tiled",
    "regather_tiles",
    "row_weights_tiled",
    "slot_liveness",
    "tiled_kernel_bytes",
    "tiled_kernel_fits",
]


def tiled_kernel_bytes(n_rt: int, n_ct: int, n_slots: int, bi: int,
                       bk: int) -> tuple:
    """(SMEM, VMEM) bytes the compiled tiled kernel needs, reserves in.

    SMEM holds the scalar-prefetched tables: ``pos`` (``n_rt * n_ct``
    words), three per-slot tables and two per-band ones, each padded to
    4 KiB.  VMEM holds the output, resident for the whole grid
    (``n_rt * bi`` f32), the double-buffered A and B tiles, the wedge
    accumulator and the ``s`` block.
    """
    def words(n: int) -> int:
        return -(-n // 1024) * 1024
    smem = 4 * (words(n_rt * n_ct) + 3 * words(n_slots)
                + words(n_rt + 1) + words(n_rt) + words(1))
    vmem = 4 * (n_rt * bi + 4 * bi * bk + bi * bi + 2 * bi)
    return smem + _SMEM_RESERVE, vmem + _VMEM_RESERVE


def tiled_kernel_fits(n_rt: int, n_ct: int, n_slots: int, bi: int,
                      bk: int) -> bool:
    """Whether the compiled tiled kernel fits one chip's SMEM and VMEM."""
    smem, vmem = tiled_kernel_bytes(n_rt, n_ct, n_slots, bi, bk)
    return smem <= SMEM_BYTES and vmem <= VMEM_BYTES


# --------------------------------------------------------------------- #
# tile-list helpers (device, traceable inside the peel loop)
# --------------------------------------------------------------------- #
def slot_liveness(tile_data: jnp.ndarray) -> jnp.ndarray:
    """int32[n_slots] — 1 where the tile still has any nonzero."""
    return (tile_data != 0).any(axis=(1, 2)).astype(jnp.int32)


def regather_tiles(tile_data: jnp.ndarray, srow: jnp.ndarray,
                   scol: jnp.ndarray, row_keep: jnp.ndarray,
                   col_keep: jnp.ndarray):
    """Tile-list regather: zero dead rows/columns inside the tiles and
    recompute per-slot liveness (the tiled DGM boundary compaction).

    ``row_keep``: (rows_pad,) 0/1 — peeled rows leave the representation
    (their wedges were fully charged when they peeled).  ``col_keep``:
    (cols_pad,) 0/1 — columns with < 2 alive neighbors cannot complete a
    wedge between alive vertices, so zeroing them never changes an alive
    pair's wedge count (the DGM exactness argument).  Shapes are static:
    slots are deactivated, never removed.
    """
    n_slots, bi, bk = tile_data.shape
    rmask = row_keep.astype(tile_data.dtype).reshape(-1, bi)[srow]
    cmask = col_keep.astype(tile_data.dtype).reshape(-1, bk)[scol]
    td = tile_data * rmask[:, :, None] * cmask[:, None, :]
    return td, slot_liveness(td)


def colsum_tiled(tile_data: jnp.ndarray, scol: jnp.ndarray,
                 n_col_tiles: int) -> jnp.ndarray:
    """Per-column degree over the tile list: float32[cols_pad]."""
    per_slot = tile_data.sum(axis=1)                     # (n_slots, bk)
    out = jnp.zeros((n_col_tiles, tile_data.shape[2]),
                    jnp.float32).at[scol].add(per_slot)
    return out.reshape(-1)


@jax.jit
def masked_colsum_tiled(tile_data: jnp.ndarray, srow: jnp.ndarray,
                        scol: jnp.ndarray, pos: jnp.ndarray,
                        s: jnp.ndarray) -> jnp.ndarray:
    """``sum_y s[y] * a[y, :]`` over the tile list: float32[cols_pad].

    With ``s`` = a peel mask this is the peeled rows' column-sum vector
    — the per-sweep wedge-accounting quantity.  Mask widths at or below
    ``_PEEL_ROW_WIDTH`` (every ordinary peel sweep) take a gathered-row
    fast path that densifies just those rows through the ``pos`` map,
    costing ``O(peel_width * n_col_tiles)`` instead of a full
    ``O(n_slots)`` pass.
    """
    n_slots, bi, bk = tile_data.shape
    n_rt, n_ct = pos.shape
    n_rows = n_rt * bi
    sf = s.reshape(n_rows).astype(jnp.float32)
    n_srows = jnp.sum((sf != 0).astype(jnp.int32))
    width = min(n_rows, _PEEL_ROW_WIDTH)

    def gathered(_):
        yidx = jnp.nonzero(sf, size=width, fill_value=0)[0]
        valid = (jnp.arange(width) < n_srows).astype(jnp.float32)
        sv = sf[yidx] * valid
        pslots = pos[(yidx // bi).astype(jnp.int32)]      # (R, n_ct)
        rows_y = (tile_data[jnp.maximum(pslots, 0),
                            (yidx % bi).astype(jnp.int32)[:, None]]
                  * (pslots >= 0).astype(jnp.float32)[:, :, None])
        return (rows_y * sv[:, None, None]).sum(axis=0).reshape(-1)

    def full(_):
        sb = sf.reshape(n_rt, bi)[srow]                   # (n_slots, bi)
        per_slot = (tile_data * sb[:, :, None]).sum(axis=1)
        return jnp.zeros((n_ct, bk), jnp.float32).at[scol].add(
            per_slot).reshape(-1)

    return jax.lax.cond(n_srows <= width, gathered, full, 0)


def row_weights_tiled(tile_data: jnp.ndarray, srow: jnp.ndarray,
                      scol: jnp.ndarray, col_w: jnp.ndarray,
                      n_row_tiles: int) -> jnp.ndarray:
    """float32[rows_pad] — ``sum_v a[u, v] * col_w[v]`` over the tiles
    (with ``col_w = dv - 1`` this is the per-vertex wedge workload the
    traversal counters charge per peel)."""
    n_slots, bi, bk = tile_data.shape
    cw = col_w.astype(jnp.float32).reshape(-1, bk)[scol]  # (n_slots, bk)
    per_slot = (tile_data * cw[:, None, :]).sum(axis=2)   # (n_slots, bi)
    out = jnp.zeros((n_row_tiles, bi), jnp.float32).at[srow].add(per_slot)
    return out.reshape(-1)


# --------------------------------------------------------------------- #
# Pallas kernel: grid (active B bands, n_slots), slot innermost
# --------------------------------------------------------------------- #
def _tiled_update_kernel(
    srow_ref,     # scalar prefetch: (n_slots,) int32 slot -> row band
    scol_ref,     # scalar prefetch: (n_slots,) int32 slot -> col band
    sptr_ref,     # scalar prefetch: (n_rt + 1,) int32 band boundaries
    pos_ref,      # scalar prefetch: (n_rt * n_ct,) int32 reverse map
    live_ref,     # scalar prefetch: (n_slots,) int32 slot liveness
    jlist_ref,    # scalar prefetch: (n_rt,) int32 B bands carrying s mass
    nact_ref,     # scalar prefetch: (1,) int32 number of such bands
    a_ref, b_ref, s_ref,
    out_ref, w_acc_ref,
    *,
    block_rows: int,
    n_col_tiles: int,
):
    jj, t = pl.program_id(0), pl.program_id(1)
    j = jlist_ref[jj]
    i = srow_ref[t]
    first = t == sptr_ref[i]
    last = t == sptr_ref[i + 1] - 1
    active = jj < nact_ref[0]

    # the whole output stays resident in VMEM for the entire grid (its
    # block index never changes) and is written back once at the end:
    # row band i is revisited once per B band, which a per-band output
    # block would lose on the chip (a revisited block is not reloaded)
    @pl.when(jnp.logical_and(jj == 0, t == 0))
    def _zero_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(first)
    def _zero_wedge_acc():
        w_acc_ref[...] = jnp.zeros_like(w_acc_ref)

    # nonzero-block skip: the MXU dot fires only when the A slot is
    # live and the mirrored B tile exists and is live (dead slots were
    # zeroed by regather_tiles, so every skip is provably a zero
    # contribution)
    bslot = pos_ref[j * n_col_tiles + scol_ref[t]]
    live = jnp.logical_and(
        jnp.logical_and(bslot >= 0, live_ref[t] > 0),
        jnp.logical_and(live_ref[jnp.maximum(bslot, 0)] > 0, active))

    @pl.when(live)
    def _accumulate():
        w_acc_ref[...] += jax.lax.dot_general(
            a_ref[0], b_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(jnp.logical_and(last, active))
    def _epilogue():
        w = w_acc_ref[...]
        bi = block_rows
        ida = i * bi + jax.lax.broadcasted_iota(jnp.int32, (bi, bi), 0)
        idb = j * bi + jax.lax.broadcasted_iota(jnp.int32, (bi, bi), 1)
        not_self = (ida != idb).astype(w.dtype)
        b2 = w * (w - 1.0) * 0.5
        contrib = b2 * not_self * s_ref[0, :][None, :]
        out_ref[pl.ds(i, 1), :] += jnp.sum(contrib, axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def butterfly_update_pallas_tiled(
    tile_data: jnp.ndarray,       # (n_slots, bi, bk) f32 tile payloads
    srow: jnp.ndarray,            # (n_slots,) int32
    scol: jnp.ndarray,            # (n_slots,) int32
    sptr: jnp.ndarray,            # (n_rt + 1,) int32
    pos: jnp.ndarray,             # (n_rt, n_ct) int32, -1 = absent
    slot_live: jnp.ndarray,       # (n_slots,) int32
    s: jnp.ndarray,               # (rows_pad,) mask over B rows
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Mask-form butterfly update over the nonzero-tile list.

    out[x] = sum_{y != x} s[y] * C((A A^T)[x, y], 2)

    ``s`` = alive mask -> per-vertex butterfly counting; ``s`` = peel
    mask -> the level-peel support delta.  Only the B row bands that
    carry ``s`` mass are visited: the grid's outer extent is their count
    (a dynamic grid bound), so a peel sweep touching a few bands costs
    ``O(bands * n_slots)`` tile pairs instead of the dense kernel's
    ``O(n_i * n_j * n_k)`` grid.  Compiled, it needs
    ``tiled_kernel_fits``; the Planner refuses a tiled plan that does
    not.
    """
    n_slots, bi, bk = tile_data.shape
    n_rt, n_ct = pos.shape
    n_rows = n_rt * bi
    has = (s.reshape(n_rt, bi) != 0).any(axis=1)
    n_act = jnp.sum(has).astype(jnp.int32)
    # active bands first; with none the single grid row is masked
    jlist = jnp.argsort(~has).astype(jnp.int32)
    kernel = functools.partial(_tiled_update_kernel, block_rows=bi,
                               n_col_tiles=n_ct)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(jnp.maximum(n_act, 1), n_slots),
        in_specs=[
            pl.BlockSpec((1, bi, bk),
                         lambda jj, t, sr, sc, sp, po, lv, jl, na: (t, 0, 0)),
            pl.BlockSpec(
                (1, bi, bk),
                lambda jj, t, sr, sc, sp, po, lv, jl, na:
                    (jnp.maximum(po[jl[jj] * n_ct + sc[t]], 0), 0, 0)),
            pl.BlockSpec((1, bi),
                         lambda jj, t, sr, sc, sp, po, lv, jl, na:
                             (0, jl[jj])),
        ],
        out_specs=pl.BlockSpec(
            (n_rt, bi), lambda jj, t, sr, sc, sp, po, lv, jl, na: (0, 0)),
        scratch_shapes=[pltpu.VMEM((bi, bi), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rt, bi), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(
        srow.astype(jnp.int32),
        scol.astype(jnp.int32),
        sptr.astype(jnp.int32),
        pos.reshape(-1).astype(jnp.int32),
        slot_live.astype(jnp.int32),
        jlist,
        n_act.reshape(1),
        tile_data.astype(jnp.float32),
        tile_data.astype(jnp.float32),
        s.reshape(1, n_rows).astype(jnp.float32),
    )
    return out.reshape(n_rows)


# --------------------------------------------------------------------- #
# jnp streaming oracle: one B band in flight, never the dense matrix
# --------------------------------------------------------------------- #
@jax.jit
def butterfly_update_tiled_xla(
    tile_data: jnp.ndarray,
    srow: jnp.ndarray,
    scol: jnp.ndarray,
    sptr: jnp.ndarray,
    pos: jnp.ndarray,
    slot_live: jnp.ndarray,
    s: jnp.ndarray,
) -> jnp.ndarray:
    """XLA twin of ``butterfly_update_pallas_tiled``, two-speed:

    * **gathered-row fast path** — when ``s`` touches at most
      ``_PEEL_ROW_WIDTH`` rows (every ordinary peel sweep), the peeled
      rows are densified straight from the tile list through the
      ``pos`` reverse map and the needed wedge columns ``W[:, peeled]``
      come from ONE vectorized broadcast-reduce over the slot list +
      a sorted segment-sum by ``srow`` — the slot-list analogue of the
      dense path's fixed-width peel-row gather, and the reason a tiled
      sweep costs ``O(n_slots * peel_width)`` instead of
      ``O(n_slots * rows_pad)``;
    * **band-streaming full path** — wider masks (the initial counting
      call's alive mask) stream over B row-bands with a fori_loop,
      computing each band's wedge column in the same vectorized form,
      skipping bands with no ``s`` mass through a ``lax.cond``.

    Peak memory is ``O(n_slots * bi * max(bi, peel_width))`` partials
    plus ``O(rows_pad * max(bi, peel_width))`` wedge columns — the
    dense ``(rows_pad, cols_pad)`` biadjacency is never materialized,
    which is what lets the xla backend serve as the tiled path's
    CPU/fallback stop above the dense memory ceiling.  Bit-identical to
    the Pallas form in the f32 integer regime (integer-valued f32
    partial sums are exact below 2^24, so accumulation order cannot
    matter).
    """
    n_slots, bi, bk = tile_data.shape
    n_rt, _n_ct = pos.shape
    n_rows = n_rt * bi
    ids = jnp.arange(n_rows, dtype=jnp.int32)
    sf = s.reshape(n_rows).astype(jnp.float32)
    s_bands = sf.reshape(n_rt, bi)
    sband = (s_bands != 0).any(axis=1)
    td = tile_data * (slot_live > 0).astype(jnp.float32)[:, None, None]
    out0 = jnp.zeros(n_rows, jnp.float32)
    n_srows = jnp.sum((sf != 0).astype(jnp.int32))
    peel_width = min(n_rows, _PEEL_ROW_WIDTH)

    def full(out):
        def band_col(j, out):
            # band j's partner tile for every slot (zero when absent):
            # partial[t] = A[band srow[t]] tile * A[band j] tile at the
            # shared column block, reduced over k — segment-summing by
            # srow yields the wedge column W[:, band_j] (column tiles
            # occupied in j but absent from srow[t]'s band contribute
            # zero either way)
            p = pos[j, scol]                              # (n_slots,)
            a_j = (td[jnp.maximum(p, 0)]
                   * (p >= 0).astype(jnp.float32)[:, None, None])
            partial = (td[:, :, None, :] * a_j[:, None, :, :]).sum(-1)
            w = jax.ops.segment_sum(
                partial, srow, num_segments=n_rt,
                indices_are_sorted=True).reshape(n_rows, bi)
            idb = j * bi + jnp.arange(bi, dtype=jnp.int32)
            not_self = (ids[:, None] != idb[None, :]).astype(jnp.float32)
            b2 = w * (w - 1.0) * 0.5
            return out + (b2 * not_self
                          * s_bands[j][None, :]).sum(axis=1)

        def band(j, out):
            return jax.lax.cond(sband[j], lambda o: band_col(j, o),
                                lambda o: o, out)
        return jax.lax.fori_loop(0, n_rt, band, out)

    def gathered(out):
        # densify the peeled rows straight from the tile list: row y
        # lives at offset y % bi of band y // bi, whose column-c tile
        # is slot pos[y // bi, c].  Padded entries repeat row 0 with
        # their s weight zeroed, so they contribute nothing.
        yidx = jnp.nonzero(sf, size=peel_width, fill_value=0)[0]
        valid = (jnp.arange(peel_width) < n_srows).astype(jnp.float32)
        sv = sf[yidx] * valid                             # (R,)
        band_of = (yidx // bi).astype(jnp.int32)
        off_of = (yidx % bi).astype(jnp.int32)
        pslots = pos[band_of]                             # (R, n_ct)
        rows_y = (td[jnp.maximum(pslots, 0), off_of[:, None]]
                  * (pslots >= 0).astype(jnp.float32)[:, :, None])
        yg = rows_y[:, scol, :].transpose(1, 0, 2)        # (n_slots, R, bk)
        partial = (td[:, :, None, :] * yg[:, None, :, :]).sum(-1)
        w = jax.ops.segment_sum(
            partial, srow, num_segments=n_rt,
            indices_are_sorted=True).reshape(n_rows, peel_width)
        not_self = (ids[:, None] != yidx[None, :]).astype(jnp.float32)
        b2 = w * (w - 1.0) * 0.5
        return out + (b2 * not_self * sv[None, :]).sum(axis=1)

    return jax.lax.cond(n_srows <= peel_width, gathered, full, out0)
