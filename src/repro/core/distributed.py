"""Distributed RECEIPT: multi-pod sharded peeling (DESIGN.md section 4).

Sharding layout (mesh axes ("pod", "data", "model") or ("data", "model")):

    A        (n_u, n_v)  rows over (pod, data), cols over model
    support  (n_u,)      over (pod, data)
    peel set A_S          gathered rows, cols over model

One CD sweep =
    gather A_S = A[rows]                    (all-gather over the dp axes)
    W = A A_S^T                             (local matmul over the model
                                             shard + all-reduce over model)
    delta = (C(W,2) masked) @ valid         (local; output stays dp-sharded)
    support' = max(support - delta, lo)     (local)

so the collective schedule per sweep is exactly: one row all-gather + one
all-reduce over `model` — RECEIPT's 1000x-fewer-sweeps is what makes this
schedule cheap (ParB would issue it ~1.5M times on TrU).

FD is a vmapped stack of independent subsets, one per device (subset dim
sharded over ALL mesh axes): ZERO collectives, the paper's independence
property preserved exactly.  ``distributed_fd_level_peel`` runs the
unified core's batched LEVEL-peel loop (engine/peel_loop.py) per shard,
with subsets LPT-assigned to devices via scheduler.lpt_shard_plan
(Graham's rule — the paper's workload-aware scheduling, Fig. 3).  It is
wired END TO END into ``receipt_fd(mesh=...)`` (DESIGN.md §4):
``shard_level_group`` lays out each shape group's survivor +
first-level stacks (load carryover across groups via
``lpt_assign(init_loads=...)``), the shard_map local body replays the
single-device launch sequence (first-level delta, then the level loop),
and the driver reconciles per-shard rho/wedge loads into one RunStats.

These functions serve four callers:
  * core/engine/fd.py — ``receipt_fd(mesh=...)``, the production driver,
  * launch/dryrun.py — .lower()/.compile() on the 512-device meshes,
  * tests/test_distributed.py — real 8-device CPU runs vs the
    single-device engine,
  * benchmarks — collective-schedule inspection.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.ref import EXACT
from ..launch.mesh import dp_axes


def _specs(mesh: Mesh):
    dp = dp_axes(mesh)
    dp = dp if len(dp) > 1 else (dp[0] if dp else None)
    return {
        "A": NamedSharding(mesh, P(dp, "model")),
        "rows": NamedSharding(mesh, P()),
        "vec_u": NamedSharding(mesh, P(dp)),
        "scalar": NamedSharding(mesh, P()),
        "a_s": NamedSharding(mesh, P(None, "model")),
    }


# --------------------------------------------------------------------- #
# CD sweep (batched peel update)
# --------------------------------------------------------------------- #
def cd_sweep_step(a, support, alive, rows, valid, ids, lo, *,
                  chunk: int = 16384):
    """One coarse peel sweep: update supports for a gathered peel set.

    a       (n_u, n_v)   0/1 residual biadjacency (rows/cols sharded)
    support (n_u,)       current supports
    alive   (n_u,)       bool
    rows    (n_s,)       int32 peel-row ids (replicated)
    valid   (n_s,)       1.0 where the row is a real peel row
    ids     (n_u,)       global row ids (= arange)
    lo      scalar       range lower bound (the Alg. 3 cap)

    The peel set is processed in CHUNKS under lax.scan so the wedge tile
    W = A A_S^T never exceeds (n_u_local, chunk) — the GSPMD analogue of
    the Pallas kernel's VMEM tiling (DESIGN.md section 2.1).  HUC
    recounts use the same op with rows = everything.
    """
    n_s = rows.shape[0]
    from ..launch.sharding import shard_act

    def delta_chunk(rows_c, valid_c):
        # A is 0/1: int8 storage quarters HBM reads and the gather's
        # cross-data reduction; the MXU runs int8 at 2x bf16 throughput.
        # Padding rows are NOT zeroed here (would force a float multiply)
        # — the `valid_c` contraction at the end nulls their contribution.
        a_s = jnp.take(a, rows_c, axis=0)               # gather peel rows
        a_s = shard_act(a_s, (None, "tp"))              # cols stay sharded
        w = jax.lax.dot_general(
            a, a_s,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,         # exact: W <= n_v
        )
        # reduce-scatter instead of all-reduce: every model rank holds the
        # same U rows, so after the contraction-psum the W chunk would be
        # replicated 16x — scattering the chunk dim halves the wire bytes
        # AND divides the C(W,2) epilogue 16x; the per-rank partial deltas
        # meet in one tiny (n_u_local,) psum.  U rows STAY dp-sharded.
        w = shard_act(w, ("batch", "tp"))
        b2 = w * (w - 1.0) * 0.5
        not_self = (ids[:, None] != rows_c[None, :]).astype(jnp.float32)
        return jnp.matmul(b2 * not_self, valid_c.astype(jnp.float32),
                          precision=EXACT)

    if n_s <= chunk:
        delta = delta_chunk(rows, valid)
    else:
        n_chunks = (n_s + chunk - 1) // chunk
        pad = n_chunks * chunk - n_s
        rows_p = jnp.pad(rows, (0, pad))
        valid_p = jnp.pad(valid, (0, pad))

        def body(acc, xs):
            rc, vc = xs
            return acc + delta_chunk(rc, vc), None

        delta, _ = jax.lax.scan(
            body,
            jnp.zeros_like(support),
            (rows_p.reshape(n_chunks, chunk), valid_p.reshape(n_chunks, chunk)),
        )

    # scatter only VALID rows (padding slots point at row 0)
    peeled = jnp.zeros_like(alive).at[rows].max(valid > 0.5) & alive
    alive_after = alive & ~peeled
    support = jnp.where(
        alive_after, jnp.maximum(support - delta, lo), support
    )
    return support, alive_after


def cd_sweep_shardmap(mesh: Mesh, *, chunk: int = 16384):
    """Explicit-collective CD sweep (shard_map): the beyond-paper
    schedule.  GSPMD lowers the chunked W psum to a full all-reduce (it
    fails to rewrite AR+slice into reduce-scatter inside the scan), which
    wires 2x the necessary bytes and computes the C(W,2) epilogue
    redundantly on every model rank.  Here the schedule is explicit:

        a_s   <- psum over dp of owner-masked rows        (s8, small)
        W_par <- local int8 dot over the n_v shard
        W     <- psum_scatter over `model`, chunk dim     (HALF the AR wire)
        delta <- local C(W,2) epilogue on 1/16 of W, then
                 psum over `model` of the (n_u_local,) partials (tiny)

    Returns a function with the same signature as cd_sweep_step.
    """
    from jax.sharding import PartitionSpec as PS

    dp = dp_axes(mesh)
    tp = "model"
    n_model = mesh.shape[tp]

    def body(a_loc, support_loc, alive_loc, rows, valid, ids_loc, lo):
        # a_loc (n_u_loc, n_v_loc) s8; rows/valid replicated
        n_u_loc = a_loc.shape[0]
        # global row offset of this dp shard
        dp_idx = jax.lax.axis_index(dp[0])
        for ax in dp[1:]:
            dp_idx = dp_idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
        row0 = dp_idx * n_u_loc
        tp_idx = jax.lax.axis_index(tp)

        n_s = rows.shape[0]
        n_chunks = max(n_s // chunk, 1)
        csz = n_s // n_chunks
        scat = csz // n_model

        def one_chunk(acc, xs):
            rows_c, valid_c = xs                       # (csz,)
            local_idx = rows_c - row0
            mine = (local_idx >= 0) & (local_idx < n_u_loc)
            a_s = jnp.where(
                mine[:, None],
                a_loc[jnp.clip(local_idx, 0, n_u_loc - 1)],
                jnp.int8(0),
            )
            a_s = jax.lax.psum(a_s, dp)                # gather peel rows
            w_par = jax.lax.dot_general(
                a_loc, a_s,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                          # (n_u_loc, csz) partial
            w = jax.lax.psum_scatter(
                w_par, tp, scatter_dimension=1, tiled=True
            )                                          # (n_u_loc, csz/16)
            rows_s = jax.lax.dynamic_slice_in_dim(rows_c, tp_idx * scat, scat)
            valid_s = jax.lax.dynamic_slice_in_dim(valid_c, tp_idx * scat, scat)
            b2 = w * (w - 1.0) * 0.5
            not_self = (ids_loc[:, None] != rows_s[None, :]).astype(jnp.float32)
            return acc + jnp.matmul(b2 * not_self, valid_s,
                                    precision=EXACT), None

        delta_par, _ = jax.lax.scan(
            one_chunk,
            jnp.zeros((n_u_loc,), jnp.float32),
            (rows.reshape(n_chunks, csz), valid.reshape(n_chunks, csz)),
        )
        delta = jax.lax.psum(delta_par, tp)            # (n_u_loc,), tiny

        peeled_loc = jnp.zeros_like(alive_loc)
        local_idx = rows - row0
        mine = (local_idx >= 0) & (local_idx < n_u_loc) & (valid > 0.5)
        peeled_loc = peeled_loc.at[
            jnp.clip(local_idx, 0, n_u_loc - 1)
        ].max(mine)
        alive_after = alive_loc & ~peeled_loc
        support_loc = jnp.where(
            alive_after, jnp.maximum(support_loc - delta, lo), support_loc
        )
        return support_loc, alive_after

    dp_spec = dp if len(dp) > 1 else dp[0]
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(PS(dp_spec, tp), PS(dp_spec), PS(dp_spec), PS(), PS(),
                  PS(dp_spec), PS()),
        out_specs=(PS(dp_spec), PS(dp_spec)),
        check_vma=False,
    )


def cd_fused_loop(a, support, alive, ids, hi, lo, *, peel_width: int,
                  max_sweeps: int = 100_000, chunk: int = 16384):
    """Device-resident CD range loop (the fused engine of core/engine/,
    sharded): peel everything with support < ``hi`` until the range drains,
    entirely inside one ``lax.while_loop`` — the host issues ONE dispatch
    per subset instead of one (plus ~8 blocking transfers) per sweep.

    Each iteration selects the peel set on device (global nonzero into a
    fixed ``peel_width`` buffer; a wider set raises the overflow flag and
    exits for the host to replay), then applies ``cd_sweep_step`` — so the
    per-sweep collective schedule (row all-gather + model-axis reduce of
    the wedge contraction) is IDENTICAL to the unfused path; fusion only
    removes the host round trips between sweeps, which is RECEIPT's
    synchronization argument applied to the dispatch layer itself.

    Returns (support, alive, rho, overflow).
    """

    def cond_fn(st):
        support, alive, rho, ovf = st
        return jnp.any(alive & (support < hi)) & (rho < max_sweeps) & ~ovf

    def body_fn(st):
        support, alive, rho, ovf = st
        peel = alive & (support < hi)
        n_peel = jnp.sum(peel)

        def on_overflow(support, alive):
            return support, alive, rho, jnp.bool_(True)

        def do_sweep(support, alive):
            rows = jnp.nonzero(peel, size=peel_width, fill_value=0)[0]
            rows = rows.astype(jnp.int32)
            valid = (jnp.arange(peel_width) < n_peel).astype(jnp.float32)
            support2, alive2 = cd_sweep_step(
                a, support, alive, rows, valid, ids, lo, chunk=chunk
            )
            return support2, alive2, rho + 1, ovf

        return jax.lax.cond(
            n_peel > peel_width, on_overflow, do_sweep, support, alive
        )

    return jax.lax.while_loop(
        cond_fn, body_fn, (support, alive, jnp.int32(0), jnp.bool_(False))
    )


def lower_cd_sweep(mesh: Mesh, *, n_u: int, n_v: int, peel_rows: int,
                   impl: str = "shardmap"):
    """Abstract-lower one production-scale CD step on ``mesh``.

    impl: "shardmap" (explicit collectives, single sweep), "gspmd"
    (single sweep), or "fused" (the whole device-resident range loop —
    ``peel_rows`` becomes the fixed peel-buffer width)."""
    sp = _specs(mesh)
    f32 = jnp.float32
    sds = jax.ShapeDtypeStruct
    if impl == "fused":
        args = (
            sds((n_u, n_v), jnp.int8),   # a (0/1: int8 storage)
            sds((n_u,), f32),            # support
            sds((n_u,), jnp.bool_),      # alive
            sds((n_u,), jnp.int32),      # ids
            sds((), f32),                # hi
            sds((), f32),                # lo
        )
        in_sh = (
            sp["A"], sp["vec_u"], sp["vec_u"], sp["vec_u"],
            sp["scalar"], sp["scalar"],
        )
        out_sh = (sp["vec_u"], sp["vec_u"], sp["scalar"], sp["scalar"])
        fn = functools.partial(cd_fused_loop, peel_width=peel_rows)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        return jitted.lower(*args)
    args = (
        sds((n_u, n_v), jnp.int8),       # a (0/1: int8 storage)
        sds((n_u,), f32),                # support
        sds((n_u,), jnp.bool_),          # alive
        sds((peel_rows,), jnp.int32),    # rows
        sds((peel_rows,), f32),          # valid
        sds((n_u,), jnp.int32),          # ids
        sds((), f32),                    # lo
    )
    in_sh = (
        sp["A"], sp["vec_u"], sp["vec_u"], sp["rows"], sp["rows"],
        sp["vec_u"], sp["scalar"],
    )
    out_sh = (sp["vec_u"], sp["vec_u"])
    fn = cd_sweep_shardmap(mesh) if impl == "shardmap" else cd_sweep_step
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    return jitted.lower(*args)


# --------------------------------------------------------------------- #
# HUC recount (full survivor recount — same op, mask = alive)
# --------------------------------------------------------------------- #
def recount_step(a, alive, ids):
    s = alive.astype(a.dtype)
    w = a @ (a * s[:, None]).T
    b2 = w * (w - 1.0) * 0.5
    not_self = (ids[:, None] != ids[None, :]).astype(a.dtype)
    return jnp.matmul(b2 * not_self, s, precision=EXACT)


# --------------------------------------------------------------------- #
# FD level-peel (engine/peel_loop.batched_level_loop sharded over groups)
# --------------------------------------------------------------------- #
def shard_fd_stack(a_stack, sup0, nmem, lo, weights, n_shards):
    """Reorder + pad an FD task stack so contiguous equal-size shards are
    LPT-balanced (scheduler.lpt_shard_plan, Graham's 4/3 rule — the
    paper's workload-aware scheduling mapped onto a mesh).

    a_stack (G, M, C); sup0 (G, M); nmem (G,); lo (G,); weights (G,)
    per-task wedge counts.  Returns (a, sup, alive, dv, lo, slots) where
    the leading dim is ``n_shards * per_shard`` and ``slots[i]`` is the
    original task index occupying stack slot i (-1 = padding slot, which
    the level loop treats as an already-finished group).
    """
    from .scheduler import lpt_shard_plan

    g_n, mm, cc = a_stack.shape
    slots, per_shard = lpt_shard_plan(list(weights), n_shards)
    n_slots = n_shards * per_shard
    a = np.zeros((n_slots, mm, cc), np.float32)
    sup = np.full((n_slots, mm), np.inf, np.float32)
    alive = np.zeros((n_slots, mm), bool)
    lo_out = np.zeros(n_slots, np.float32)
    for s, t in enumerate(slots):
        if t < 0:
            continue
        a[s] = a_stack[t]
        sup[s] = sup0[t]
        alive[s, : int(nmem[t])] = True
        lo_out[s] = lo[t]
    dv = a.sum(axis=1)
    return a, sup, alive, dv, lo_out, np.asarray(slots)


def shard_level_group(built: dict, n_shards: int, init_loads=None):
    """Reorder one FD shape group's level stacks into the LPT shard layout.

    ``built`` is `engine/fd.build_level_stack` output (survivor stack +
    first-level stack + per-subset metadata).  Tasks are LPT-assigned to
    ``n_shards`` equal-size contiguous shards by their static wedge
    bound (``scheduler.lpt_shard_plan`` — Graham's 4/3 rule, the paper's
    workload-aware scheduling mapped onto the mesh); ``init_loads``
    carries accumulated shard loads across shape groups so the whole-run
    assignment balances, not just each group's.  Padding slots are dead
    groups (``alive`` all False, ``sup`` all inf) the level loop no-ops
    over.

    Returns (arrays, slots): ``arrays`` has the
    ``distributed_fd_level_peel`` inputs plus ``per_shard`` and
    ``shard_load`` (this group's static wedge mass per shard);
    ``slots[s]`` is the group-list index occupying stack slot ``s``
    (-1 = padding).
    """
    from .scheduler import lpt_shard_plan

    group = built["group"]
    weights = [t["wedges"] for t in group]
    slots, per_shard = lpt_shard_plan(weights, n_shards, init_loads)
    n_slots = n_shards * per_shard
    mm, cc, w1 = built["mm"], built["cc"], built["w1"]
    a = np.zeros((n_slots, mm, cc), np.float32)
    a_l1 = np.zeros((n_slots, w1, cc), np.float32)
    sup = np.full((n_slots, mm), np.inf, np.float32)
    alive = np.zeros((n_slots, mm), bool)
    n_l1 = np.zeros(n_slots, np.int32)
    cap1 = np.full(n_slots, -np.inf, np.float32)
    lo = np.zeros(n_slots, np.float32)
    for s, t in enumerate(slots):
        if t < 0:
            continue
        a[s] = built["a"][t]
        a_l1[s] = built["a_l1"][t]
        sup[s] = built["sup0"][t]
        alive[s] = built["alive0"][t]
        n_l1[s] = built["n_l1"][t]
        cap1[s] = built["cap1"][t]
        lo[s] = built["los"][t]
    dv = a.sum(axis=1)
    shard_load = np.array([
        sum(weights[t] for t in slots[i * per_shard:(i + 1) * per_shard]
            if t >= 0)
        for i in range(n_shards)
    ], np.float64)
    return dict(a=a, a_l1=a_l1, sup=sup, alive=alive, dv=dv, n_l1=n_l1,
                cap1=cap1, lo=lo, per_shard=per_shard,
                shard_load=shard_load), np.asarray(slots)


def fd_level_shardmap(mesh: Mesh, *, max_sweeps: int = 100_000,
                      update_mode: str = "b2",
                      peel_width: Optional[int] = None,
                      full_state: bool = False):
    """Batched level-peel with the group dim sharded over EVERY mesh axis:
    each device runs the unified peel core's level loop on its local
    shard with ZERO collectives (shard_map makes the paper's subset
    independence explicit — each shard's while_loop exits as soon as ITS
    groups drain, no global any(alive) all-reduce per sweep).

    The local body is the SAME launch sequence as the single-device FD
    driver (`engine/fd._run_level_groups`): apply the host pre-peel's
    first-level support delta (group-local — L1 rows are distinct
    vertices from the survivor rows, so no self-pair masking), then run
    ``batched_level_loop`` with the group's update mode and peel width.
    Callers without a first level pass ``n_l1 = 0`` / ``cap1 = -inf``
    (the delta and the floor both become no-ops).

    Returns a function (a, a_l1, n_l1, cap1, sup, alive, dv, lo) ->
    (theta, rho, wedges), or with ``full_state=True`` the whole carried
    state (sup, alive, dv, theta, rho, wedges) so the end-to-end driver
    can re-enter after a ``max_sweeps`` cap-exit.
    """
    from .engine.peel_loop import batched_level_loop

    all_axes = tuple(mesh.axis_names)

    def local(a, a_l1, n_l1, cap1, sup, alive, dv, lo):
        f32 = jnp.float32
        valid1 = (jnp.arange(a_l1.shape[1])[None, :]
                  < n_l1[:, None]).astype(f32)
        w1 = jnp.einsum("gmc,gwc->gmw", a.astype(f32), a_l1.astype(f32))
        delta1 = jnp.einsum("gmw,gw->gm", w1 * (w1 - 1.0) * 0.5, valid1,
                            precision=EXACT)
        sup = jnp.maximum(sup - delta1, cap1[:, None])
        row_ext = jnp.zeros(a.shape[:2], jnp.int32)   # xla path ignores it
        pw = a.shape[1] if peel_width is None else min(peel_width,
                                                       a.shape[1])
        (sup2, alive2, dv2, theta, rho, wedges, _max_level,
         _sweeps) = batched_level_loop(
            a, row_ext, sup, alive, dv, lo,
            # pinned on purpose: the local body is pure jnp (DESIGN.md
            # §4), one program per shard, no Pallas launch in shard_map
            backend="xla", blocks=(8, 8, 8),
            peel_width=pw, max_sweeps=max_sweeps,
            update_mode=update_mode,
        )
        if full_state:
            return sup2, alive2, dv2, theta, rho, wedges
        return theta, rho, wedges

    vec = P(all_axes, None)
    g1 = P(all_axes)
    out_specs = ((vec, vec, vec, vec, g1, g1) if full_state
                 else (vec, g1, g1))
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(all_axes, None, None), P(all_axes, None, None),
                  g1, g1, vec, vec, vec, g1),
        out_specs=out_specs,
        check_vma=False,
    )


@functools.lru_cache(maxsize=64)
def _fd_level_jitted(mesh: Mesh, max_sweeps: int, update_mode: str,
                     peel_width: Optional[int], full_state: bool):
    """Compile-once cache for the sharded level loop.  jax's jit cache is
    keyed on FUNCTION IDENTITY, and both ``fd_level_shardmap`` and
    ``jax.jit`` build fresh closures — without this cache every
    shape-group dispatch and every cap-exit re-entry of the mesh FD
    driver would retrace and recompile an identical program."""
    all_axes = tuple(mesh.axis_names)
    stack = NamedSharding(mesh, P(all_axes, None, None))
    vec = NamedSharding(mesh, P(all_axes, None))
    g1 = NamedSharding(mesh, P(all_axes))
    fn = fd_level_shardmap(mesh, max_sweeps=max_sweeps,
                           update_mode=update_mode, peel_width=peel_width,
                           full_state=full_state)
    out_sh = ((vec, vec, vec, vec, g1, g1) if full_state
              else (vec, g1, g1))
    return jax.jit(
        fn,
        in_shardings=(stack, stack, g1, g1, vec, vec, vec, g1),
        out_shardings=out_sh,
    )


def fd_stack_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of an FD stack's leading (group) dim over every mesh
    axis.  Pre-placing the big biadjacency stack with ``jax.device_put``
    lets cap-exit re-entries reuse the device-resident copy instead of
    re-uploading the padded host array every time."""
    return NamedSharding(mesh, P(tuple(mesh.axis_names), None, None))


def distributed_fd_level_peel(mesh: Mesh, a, sup, alive, dv, lo, *,
                              a_l1=None, n_l1=None, cap1=None,
                              update_mode: str = "b2",
                              peel_width: Optional[int] = None,
                              max_sweeps: int = 100_000,
                              full_state: bool = False):
    """Run the sharded FD level-peel on a live mesh.

    Inputs are the ``shard_fd_stack`` / ``shard_level_group`` layout
    (leading dim divisible by ``mesh.size``).  ``a_l1`` / ``n_l1`` /
    ``cap1`` carry the host pre-peel's first level (optional — omitted
    means no first-level delta is applied).  Returns (theta, rho,
    wedges) per stack slot — or the full carried state (sup, alive, dv,
    theta, rho, wedges) with ``full_state=True``, which the end-to-end
    driver (`engine/fd._run_level_groups_mesh`) feeds back on a
    ``max_sweeps`` cap-exit.  The caller maps slots back to tasks via
    the plan's ``slots`` array.
    """
    f32 = jnp.float32
    g_n, _mm, cc = a.shape
    if a_l1 is None:
        a_l1 = np.zeros((g_n, 8, cc), np.float32)
        n_l1 = np.zeros(g_n, np.int32)
        cap1 = np.full(g_n, -np.inf, np.float32)
    jitted = _fd_level_jitted(mesh, max_sweeps, update_mode, peel_width,
                              full_state)
    with mesh:
        return jitted(
            jnp.asarray(a, f32), jnp.asarray(a_l1, f32),
            jnp.asarray(n_l1, jnp.int32), jnp.asarray(cap1, f32),
            jnp.asarray(sup, f32), jnp.asarray(alive),
            jnp.asarray(dv, f32), jnp.asarray(lo, f32),
        )


# --------------------------------------------------------------------- #
# FD stack (independent subsets, one per device)
# --------------------------------------------------------------------- #
def fd_stack_step(a_stack, sup0, n_members, lo):
    """Peel a stack of independent induced subgraphs (vmap over subsets).

    a_stack (G, M, C); sup0 (G, M); n_members (G,); lo (G,).
    Subset dim G is sharded over every mesh axis -> zero collectives.
    """
    def peel_one(a_sub, sup, nm, lo1):
        w = a_sub @ a_sub.T
        b2 = w * (w - 1.0) * 0.5
        mm = a_sub.shape[0]
        b2 = b2 * (1.0 - jnp.eye(mm, dtype=a_sub.dtype))

        def body(t, st):
            s, alive, theta = st
            masked = jnp.where(alive, s, jnp.inf)
            u = jnp.argmin(masked)
            th = jnp.maximum(masked[u], lo1)
            do = t < nm
            theta = jnp.where(do, theta.at[u].set(th), theta)
            s = jnp.where(do & alive, jnp.maximum(s - b2[u], th), s)
            alive = jnp.where(do, alive.at[u].set(False), alive)
            return s, alive, theta

        alive0 = jnp.arange(mm) < nm
        _, _, theta = jax.lax.fori_loop(
            0, mm, body, (sup, alive0, jnp.zeros_like(sup))
        )
        return theta

    return jax.vmap(peel_one)(a_stack, sup0, n_members, lo)


def lower_fd_stack(mesh: Mesh, *, n_subsets: int, rows: int, cols: int):
    """FD subsets are independent -> shard_map makes that EXPLICIT: each
    device peels its local stack with zero collectives.  (Left to GSPMD,
    the per-step batched argmin/gather lowered to ~12k tiny all-reduces —
    EXPERIMENTS.md §Roofline notes.)"""
    all_axes = tuple(mesh.axis_names)
    stack = NamedSharding(mesh, P(all_axes, None, None))
    vec = NamedSharding(mesh, P(all_axes, None))
    g1 = NamedSharding(mesh, P(all_axes))
    local_fd = jax.shard_map(
        fd_stack_step, mesh=mesh,
        in_specs=(P(all_axes, None, None), P(all_axes, None),
                  P(all_axes), P(all_axes)),
        out_specs=P(all_axes, None),
        check_vma=False,
    )
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    args = (
        sds((n_subsets, rows, cols), f32),
        sds((n_subsets, rows), f32),
        sds((n_subsets,), jnp.int32),
        sds((n_subsets,), f32),
    )
    jitted = jax.jit(
        local_fd,
        in_shardings=(stack, vec, g1, g1),
        out_shardings=vec,
    )
    return jitted.lower(*args)


# --------------------------------------------------------------------- #
# runnable multi-device engine (tests / small clusters)
# --------------------------------------------------------------------- #
def distributed_butterfly_support(mesh: Mesh, a: jnp.ndarray, s: jnp.ndarray):
    """Counting/recount on a live mesh: support[i] = sum_{j!=i} s_j C(W_ij, 2)."""
    sp = _specs(mesh)
    n_u = a.shape[0]
    ids = jnp.arange(n_u, dtype=jnp.int32)

    def f(a, s, ids):
        return recount_step(a, s > 0.5, ids)

    jitted = jax.jit(
        f,
        in_shardings=(sp["A"], sp["vec_u"], sp["vec_u"]),
        out_shardings=sp["vec_u"],
    )
    with mesh:
        return jitted(a, s, ids)


def distributed_cd_fused_loop(mesh: Mesh, a, support, alive, hi, lo, *,
                              peel_width: int, max_sweeps: int = 100_000,
                              chunk: int = 16384):
    """Run a whole device-resident CD range loop on a live mesh (one
    dispatch; the multi-device twin of the engine's ``device_peel_loop``).

    Returns (support, alive, rho, overflow)."""
    sp = _specs(mesh)
    n_u = a.shape[0]
    ids = jnp.arange(n_u, dtype=jnp.int32)
    fn = functools.partial(
        cd_fused_loop, peel_width=peel_width, max_sweeps=max_sweeps,
        chunk=chunk,
    )
    jitted = jax.jit(
        fn,
        in_shardings=(sp["A"], sp["vec_u"], sp["vec_u"], sp["vec_u"],
                      sp["scalar"], sp["scalar"]),
        out_shardings=(sp["vec_u"], sp["vec_u"], sp["scalar"], sp["scalar"]),
    )
    with mesh:
        return jitted(
            a.astype(jnp.int8), support, alive, ids,
            jnp.asarray(hi, jnp.float32), jnp.asarray(lo, jnp.float32),
        )


def distributed_cd_sweep(mesh: Mesh, a, support, alive, rows, valid, lo,
                         impl: str = "gspmd", chunk: int = 16384):
    sp = _specs(mesh)
    n_u = a.shape[0]
    ids = jnp.arange(n_u, dtype=jnp.int32)
    if impl == "shardmap":
        fn = cd_sweep_shardmap(mesh, chunk=chunk)
    else:
        fn = cd_sweep_step
    jitted = jax.jit(
        fn,
        in_shardings=(sp["A"], sp["vec_u"], sp["vec_u"], sp["rows"],
                      sp["rows"], sp["vec_u"], sp["scalar"]),
        out_shardings=(sp["vec_u"], sp["vec_u"]),
    )
    with mesh:
        return jitted(a.astype(jnp.int8), support, alive, rows, valid, ids, lo)
