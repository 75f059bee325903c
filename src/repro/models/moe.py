"""Mixture-of-Experts FFN (DeepSeek-V2/V3 style: shared + routed experts).

Dispatch is index-based (argsort by expert id -> capacity-bounded gather ->
grouped einsum -> scatter back), the standard TPU-friendly formulation:
the (E, C, d) dispatched tensor is annotated for expert parallelism so
GSPMD lowers the dispatch/combine into all_to_all over the `model` axis.

Routing variants:
  * "softmax_topk"  — V2: softmax over routed experts, top-k, optional
                      load-balance aux loss.
  * "sigmoid_bias"  — V3: sigmoid affinities + learned per-expert bias
                      added for *selection only* (aux-loss-free balancing,
                      DeepSeek [arXiv:2408.15664]); gates renormalized over
                      the selected experts.

Not modeled (noted per DESIGN.md): node-limited / group-limited routing
(a deployment constraint, orthogonal to the math).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init

Params = Dict[str, Any]


def init_moe(
    key,
    d: int,
    d_ff: int,
    n_routed: int,
    n_shared: int,
    d_ff_shared: Optional[int] = None,
    dtype=jnp.float32,
) -> Params:
    """Routed experts stored stacked: (E, d, f) / (E, f, d)."""
    ks = jax.random.split(key, 5)
    d_ff_shared = d_ff_shared or d_ff * max(n_shared, 1)
    p = {
        "router": dense_init(ks[0], d, n_routed, jnp.float32),
        "router_bias": jnp.zeros((n_routed,), jnp.float32),
        "gate": (
            jax.random.normal(ks[1], (n_routed, d, d_ff), jnp.float32) / d**0.5
        ).astype(dtype),
        "up": (
            jax.random.normal(ks[2], (n_routed, d, d_ff), jnp.float32) / d**0.5
        ).astype(dtype),
        "down": (
            jax.random.normal(ks[3], (n_routed, d_ff, d), jnp.float32) / d_ff**0.5
        ).astype(dtype),
    }
    if n_shared > 0:
        from .layers import init_swiglu

        p["shared"] = init_swiglu(ks[4], d, d_ff_shared, dtype)
    return p


def route(
    p: Params,
    x2d: jnp.ndarray,               # (T, d) flattened tokens
    *,
    top_k: int,
    mode: str = "softmax_topk",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (expert_idx (T, k), gates (T, k), aux_loss scalar)."""
    logits = (x2d.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    n_e = logits.shape[-1]
    if mode == "sigmoid_bias":
        aff = jax.nn.sigmoid(logits)
        sel_score = aff + p["router_bias"][None, :]
        _, idx = jax.lax.top_k(sel_score, top_k)
        gates = jnp.take_along_axis(aff, idx, axis=-1)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        aux = jnp.zeros((), jnp.float32)          # aux-loss-free balancing
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(probs, top_k)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        # Switch-style load-balance loss
        me = probs.mean(0)
        ce = jnp.zeros((n_e,)).at[idx.reshape(-1)].add(1.0) / idx.size
        aux = n_e * jnp.sum(me * ce)
    return idx, gates.astype(x2d.dtype), aux


def _dispatch_group(x2d, idx, gates, n_e: int, cap: int):
    """Dispatch ONE token group to (E, cap, d) + return combine metadata.

    Runs entirely on local data (vmapped over groups), so no collective
    is needed until the (G, E, C, d) tensor re-shards E over the model
    axis — which GSPMD lowers to exactly one all_to_all (the EP exchange).
    """
    t, d = x2d.shape
    top_k = idx.shape[-1]
    flat_e = idx.reshape(-1)                       # (t*k,)
    flat_tok = jnp.repeat(jnp.arange(t), top_k)
    flat_gate = gates.reshape(-1)
    order = jnp.argsort(flat_e)
    se, stok, sgate = flat_e[order], flat_tok[order], flat_gate[order]

    seg_start = jnp.concatenate([jnp.zeros(1, se.dtype), se[:-1]]) != se
    start_of_seg = jax.lax.cummax(
        jnp.where(seg_start, jnp.arange(t * top_k), 0)
    )
    pos_in_seg = jnp.arange(t * top_k) - start_of_seg
    keep = pos_in_seg < cap
    slot = jnp.where(keep, se * cap + pos_in_seg, n_e * cap)
    disp = jnp.zeros((n_e * cap + 1, d), x2d.dtype).at[slot].add(
        x2d[stok] * keep[:, None].astype(x2d.dtype)
    )
    return disp[:-1].reshape(n_e, cap, d), (slot, stok, sgate, keep)


def _combine_group(eout, meta, t: int):
    slot, stok, sgate, keep = meta
    n_e, cap, d = eout.shape
    eout2d = eout.reshape(n_e * cap, d)
    pair_out = eout2d[jnp.where(keep, slot, 0)] * (
        sgate * keep.astype(sgate.dtype)
    )[:, None]
    return jnp.zeros((t, d), eout.dtype).at[stok].add(pair_out)


def moe_forward_sharded(
    p: Params,
    x: jnp.ndarray,                 # (B, S, d); batch over dp, seq over model
    *,
    top_k: int,
    capacity_factor: float,
    mode: str,
    no_drop: bool,
    mesh,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Explicit-collective MoE block (shard_map).

    Each device routes + dispatches ONLY its local (b_loc x s_loc) tokens;
    the expert exchange is one explicit all_to_all pair over `model`
    (split the expert axis out, concat the token axis), and the FSDP
    weight shards are all-gathered over the dp axes once per layer.
    GSPMD could not keep the data-dependent sort/gather chain sharded
    (measured 158 TB/step of all-reduce on the v3 train cell when the
    dispatch was expressed at the global level — EXPERIMENTS.md §Perf);
    making the schedule explicit removes every collective except:

        all_to_all  (B_loc*S_loc tokens, bf16)   x2      (EP exchange)
        all-gather  (expert weight shards)       x3      (FSDP)
        psum        (aux scalar)
    """
    from jax.sharding import PartitionSpec as PS

    from ..launch.mesh import axis_size, dp_axes

    dp = dp_axes(mesh)
    tp = "model"
    n_model = mesh.shape[tp]
    n_dp = axis_size(mesh, dp)
    b, s, d = x.shape
    n_e = p["router"].shape[-1]
    b_loc, s_loc = b // n_dp, s // n_model
    t_loc = b_loc * s_loc
    e_loc = n_e // n_model
    cap = t_loc if no_drop else max(
        int(t_loc * top_k / n_e * capacity_factor), 1
    )

    def body(x_loc, pl):
        x2 = x_loc.reshape(t_loc, d)
        idx, gates, aux = route(
            {"router": pl["router"], "router_bias": pl["router_bias"]},
            x2, top_k=top_k, mode=mode,
        )
        disp, meta = _dispatch_group(x2, idx, gates, n_e, cap)  # (E, cap, d)
        # EP exchange: every rank keeps its E/n_model experts' slices
        disp = jax.lax.all_to_all(
            disp, tp, split_axis=0, concat_axis=1, tiled=True
        )                                                   # (E_loc, n*cap, d)
        # FSDP: gather the dp-sharded d/f dims of this rank's experts
        gate_w = jax.lax.all_gather(pl["gate"], dp, axis=1, tiled=True)
        up_w = jax.lax.all_gather(pl["up"], dp, axis=1, tiled=True)
        down_w = jax.lax.all_gather(pl["down"], dp, axis=2, tiled=True)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, gate_w))
        u = jnp.einsum("ecd,edf->ecf", disp, up_w)
        eout = jnp.einsum("ecf,efd->ecd", g * u, down_w)
        eout = jax.lax.all_to_all(
            eout, tp, split_axis=1, concat_axis=0, tiled=True
        )                                                   # (E, cap, d)
        out2 = _combine_group(eout, meta, t_loc)
        if "shared" in pl:
            # shared experts: tokens are sharded over BOTH dp (batch) and
            # tp (seq), so an f-partial psum over `model` would mix
            # different ranks' tokens — instead gather the (small) shared
            # weights fully and compute token-locally.
            sh = pl["shared"]
            gate_s = jax.lax.all_gather(
                jax.lax.all_gather(sh["gate"], dp, axis=0, tiled=True),
                tp, axis=1, tiled=True)
            up_s = jax.lax.all_gather(
                jax.lax.all_gather(sh["up"], dp, axis=0, tiled=True),
                tp, axis=1, tiled=True)
            down_s = jax.lax.all_gather(
                jax.lax.all_gather(sh["down"], tp, axis=0, tiled=True),
                dp, axis=1, tiled=True)
            gs_ = jax.nn.silu(x2 @ gate_s) * (x2 @ up_s)
            out2 = out2 + gs_ @ down_s
        aux = jax.lax.pmean(aux, (*dp, tp))
        return out2.reshape(b_loc, s_loc, d), aux

    dp_spec = dp if len(dp) > 1 else dp[0]
    pspecs = {
        "router": PS(), "router_bias": PS(),
        "gate": PS(tp, dp_spec, None),
        "up": PS(tp, dp_spec, None),
        "down": PS(tp, None, dp_spec),
    }
    if "shared" in p:
        pspecs["shared"] = {
            "gate": PS(dp_spec, tp),
            "up": PS(dp_spec, tp),
            "down": PS(tp, dp_spec),
        }
    pl = {k: p[k] for k in pspecs}
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(PS(dp_spec, tp, None), pspecs),
        out_specs=(PS(dp_spec, tp, None), PS()),
        check_vma=False,
    )
    return fn(x, pl)


def moe_forward(
    p: Params,
    x: jnp.ndarray,                 # (B, S, d)
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    mode: str = "softmax_topk",
    ep_constraint: Optional[Callable] = None,
    no_drop: bool = False,
    group_size: int = 256,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (out (B, S, d), aux_loss).

    Dispatch is GROUP-BLOCKED along (batch x seq-blocks), double-vmapped:
    with group_size aligned to the sequence-parallel shard (s / TP), every
    group's route/sort/dispatch is DEVICE-LOCAL — no global argsort, no
    all-gather of the token tensor, no de-sharding of the seq axis
    (measured 5.3 TB/step of f32 token all-gathers on the v3 train cell
    before seq-local grouping; see EXPERIMENTS.md §Perf).  The only MoE
    collectives left are the (B, G, E, C, d) all_to_all pair that moves
    the expert axis onto `model` and back.  Capacity is per-group
    (group_size * k / E * factor) so total dispatch FLOPs are unchanged;
    per-group skew is absorbed by capacity_factor (drops are the standard
    MoE-training trade and are disabled on the decode path).

    ep_constraint: override for the dispatch-tensor sharding pin.
    """
    from ..launch.mesh import axis_size, dp_axes
    from ..launch.sharding import current_mesh

    b, s, d = x.shape
    n_e = p["router"].shape[-1]

    # distributed path: explicit shard_map schedule when a mesh context is
    # active and the shapes divide it (training / prefill cells)
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        n_model = mesh.shape["model"]
        n_dp = axis_size(mesh, dp_axes(mesh))
        if (b % n_dp == 0 and s % n_model == 0 and n_e % n_model == 0
                and s >= n_model):
            return moe_forward_sharded(
                p, x, top_k=top_k, capacity_factor=capacity_factor,
                mode=mode, no_drop=no_drop, mesh=mesh,
            )

    gs = min(group_size, s)
    n_g = s // gs
    assert n_g * gs == s, f"seq {s} not divisible by group {gs}"

    if no_drop:
        cap = gs                     # worst case: all of a group's tokens
    else:
        cap = max(int(gs * top_k / n_e * capacity_factor), 1)

    xg = x.reshape(b, n_g, gs, d)

    def group(xx):                   # (gs, d) -> local route + dispatch
        idx, gates, aux = route(p, xx, top_k=top_k, mode=mode)
        disp, meta = _dispatch_group(xx, idx, gates, n_e, cap)
        return disp, meta, aux

    disp, meta, aux = jax.vmap(jax.vmap(group))(xg)
    aux = jnp.mean(aux)

    if ep_constraint is None:
        from ..launch.sharding import shard_act

        ep_constraint = lambda t: shard_act(
            t, ("batch", None, "expert", None, None)
        )
    disp = ep_constraint(disp)       # (B, G, E, C, d): the EP all_to_all

    # grouped expert FFN (SwiGLU) — E-sharded, (B, G)-sharded, local
    g = jax.nn.silu(jnp.einsum("bgecd,edf->bgecf", disp, p["gate"]))
    u = jnp.einsum("bgecd,edf->bgecf", disp, p["up"])
    eout = jnp.einsum("bgecf,efd->bgecd", g * u, p["down"])
    eout = ep_constraint(eout)       # inverse EP all_to_all

    out = jax.vmap(jax.vmap(lambda ee, mm: _combine_group(ee, mm, gs)))(
        eout, meta
    )
    from ..launch.sharding import shard_act as _sa

    out = _sa(out.reshape(b, s, d), ("batch", "sp", None))

    if "shared" in p:
        from .layers import swiglu

        out = out + swiglu(p["shared"], x)
    return out, aux
