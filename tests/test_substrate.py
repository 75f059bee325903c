"""Substrate tests: optimizer, train step, checkpoint, scheduler,
fault-tolerance logic, sharding rules."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.scheduler import lpt_assign, pack_by_shape
from repro.train.checkpoint import CheckpointManager
from repro.train.fault_tolerance import ElasticMesh, RestartManager, StragglerMonitor
from repro.train.optimizer import (
    AdamWConfig, adamw_init, adamw_update, compress_int8, decompress_int8, lr_at,
)
from repro.train.train_step import init_train_state, make_train_step


# --------------------------------------------------------------------- #
# optimizer
# --------------------------------------------------------------------- #
def _quad_loss(params, batch):
    err = params["w"] - batch["target"]
    return jnp.sum(err * err), {}


def test_adamw_converges_on_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      total_steps=1000, schedule="constant")
    params = {"w": jnp.zeros((4,))}
    state = init_train_state(params, cfg)
    step = jax.jit(make_train_step(_quad_loss, cfg))
    batch = {"target": jnp.array([1.0, -2.0, 3.0, 0.5])}
    for _ in range(300):
        state, metrics = step(state, batch)
    np.testing.assert_allclose(
        np.asarray(state["params"]["w"]), np.asarray(batch["target"]), atol=1e-2
    )


def test_grad_clip_bounds_update():
    cfg = AdamWConfig(lr=1.0, grad_clip=1e-3, weight_decay=0.0,
                      warmup_steps=0, schedule="constant")
    params = {"w": jnp.array([0.0])}
    opt = adamw_init(params, cfg)
    grads = {"w": jnp.array([1e9])}
    _, _, m = adamw_update(params, grads, opt, cfg)
    assert float(m["grad_norm"]) > 1e8  # reported pre-clip


def test_lr_schedule_warmup_and_decay():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110)
    assert float(lr_at(cfg, jnp.asarray(0))) == 0.0
    assert abs(float(lr_at(cfg, jnp.asarray(10))) - 1.0) < 1e-6
    assert float(lr_at(cfg, jnp.asarray(110))) < 1e-6


def test_microbatch_accumulation_matches_full_batch():
    cfg = AdamWConfig(lr=0.01, weight_decay=0.0, warmup_steps=0,
                      schedule="constant")
    params = {"w": jnp.ones((3,))}

    def loss(p, b):
        return jnp.mean((p["w"] * b["x"] - b["y"]) ** 2), {}

    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32)),
        "y": jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32)),
    }
    s1 = init_train_state(params, cfg)
    s2 = init_train_state(params, cfg)
    full = jax.jit(make_train_step(loss, cfg))
    micro = jax.jit(make_train_step(loss, cfg, microbatches=4))
    s1, _ = full(s1, batch)
    s2, _ = micro(s2, batch)
    np.testing.assert_allclose(
        np.asarray(s1["params"]["w"]), np.asarray(s2["params"]["w"]),
        rtol=1e-5, atol=1e-6,
    )


def test_int8_compression_error_feedback():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    err = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    # accumulated dequantized grads + error feedback converge to true sum
    acc_err = err
    for _ in range(50):
        q, s, acc_err = compress_int8(g, acc_err)
        total = total + decompress_int8(q, s)
    np.testing.assert_allclose(
        np.asarray(total) / 50, np.asarray(g), atol=2e-2
    )


# --------------------------------------------------------------------- #
# checkpoint
# --------------------------------------------------------------------- #
def _state():
    return {
        "params": {"a": jnp.arange(6.0).reshape(2, 3), "b": [jnp.ones(4)]},
        "opt": {"step": jnp.asarray(7, jnp.int32)},
    }


def test_checkpoint_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        ck = CheckpointManager(d)
        s = _state()
        ck.save(5, s)
        r = ck.restore(jax.eval_shape(lambda: s))
        for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(r)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity_tmp_ignored():
    with tempfile.TemporaryDirectory() as d:
        ck = CheckpointManager(d)
        ck.save(1, _state())
        # a torn write (tmp dir without rename) must be invisible
        os.makedirs(os.path.join(d, "step_9.tmp"))
        assert ck.latest_step() == 1


def test_checkpoint_gc_keeps_latest():
    with tempfile.TemporaryDirectory() as d:
        ck = CheckpointManager(d, keep=2)
        for step in (1, 2, 3, 4):
            ck.save(step, _state())
        assert ck.all_steps() == [3, 4]


def test_checkpoint_async():
    with tempfile.TemporaryDirectory() as d:
        ck = CheckpointManager(d)
        ck.save(1, _state(), blocking=False)
        ck.wait()
        assert ck.latest_step() == 1


def test_restart_manager_resume():
    with tempfile.TemporaryDirectory() as d:
        rm = RestartManager(CheckpointManager(d), save_every=2)
        s = _state()
        rm.maybe_save(2, s, blocking=True)
        template = jax.eval_shape(lambda: s)
        restored, step = rm.resume_or_init(template)
        assert step == 2
        np.testing.assert_array_equal(
            np.asarray(restored["params"]["a"]), np.asarray(s["params"]["a"])
        )


# --------------------------------------------------------------------- #
# scheduler / straggler
# --------------------------------------------------------------------- #
def test_lpt_assign_balances():
    w = [10, 9, 8, 2, 2, 2, 1]
    plan = lpt_assign(w, 2)
    loads = [sum(w[i] for i in grp) for grp in plan]
    assert abs(loads[0] - loads[1]) <= 4  # LPT bound for this instance


@settings(max_examples=30, deadline=None)
@given(
    weights=st.lists(st.floats(0.1, 100), min_size=1, max_size=40),
    k=st.integers(1, 8),
)
def test_property_lpt_is_complete_and_bounded(weights, k):
    plan = lpt_assign(weights, k)
    seen = sorted(i for grp in plan for i in grp)
    assert seen == list(range(len(weights)))       # every task placed once
    loads = [sum(weights[i] for i in grp) for grp in plan]
    # directly provable greedy bound: the last job assigned to the max
    # worker started no later than avg, so
    #   max_load <= sum/k + max_w * (k-1)/k
    # (Graham's 4/3 holds vs OPT, which is NOT certifiable from a lower
    # bound — hypothesis found the counterexample; see git history)
    bound = sum(weights) / k + max(weights) * (k - 1) / k
    assert max(loads) <= bound + 1e-6


def test_pack_by_shape_groups_and_orders():
    tasks = [
        {"r": 5, "c": 5, "w": 1},
        {"r": 6, "c": 7, "w": 9},
        {"r": 30, "c": 3, "w": 4},
    ]
    groups = pack_by_shape(
        tasks,
        size_of=lambda t: (t["r"], t["c"]),
        weight_of=lambda t: t["w"],
        bucket=lambda n: 8 if n <= 8 else 32,
    )
    # two groups: (8,8) and (32,8); heaviest-first inside
    assert len(groups) == 2
    small = [g for g in groups if len(g) == 2][0]
    assert small[0]["w"] >= small[1]["w"]


def test_straggler_monitor_flags_slow_task():
    mon = StragglerMonitor(threshold=2.0)
    for t in range(6):
        mon.record(f"task{t}", 1.0)
    mon.record("slow", 10.0)
    assert "slow" in mon.stragglers()
    plan = mon.speculative_plan([f"task{t}" for t in range(6)] + ["slow"], 3)
    placed = [i for grp in plan for i in grp]
    assert len(placed) >= 7                        # duplicate scheduled


def test_elastic_mesh_shrinks_preserving_model_axis():
    class FakeDev:
        def __init__(self, i):
            self.id = i

    em = ElasticMesh([FakeDev(i) for i in range(8)], model_axis=2)
    m = em.make_mesh()
    assert m.shape["model"] == 2 and m.shape["data"] == 4
    em.mark_failed([6, 7])
    m2 = em.make_mesh()
    assert m2.shape["model"] == 2 and m2.shape["data"] == 3


# --------------------------------------------------------------------- #
# sharding rules
# --------------------------------------------------------------------- #
def test_sharding_rules_divisibility_fallback():
    import re

    from repro.launch.mesh import make_mesh  # noqa: F401
    from repro.launch.sharding import _check_div

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 2}

    spec = _check_div((6, 8), ("data", "model"), FakeMesh())
    # 6 % 4 != 0 -> dropped; 8 % 2 == 0 -> kept
    assert spec == jax.sharding.PartitionSpec(None, "model")


def test_lm_param_specs_match_paths():
    from repro.configs import get_bundle

    b = get_bundle("deepseek-v3-671b", reduced=True)
    # use a fake mesh-like object compatible with _check_div/axis_size
    import jax as _jax

    mesh = _jax.make_mesh((1, 1), ("data", "model"))
    specs = b.param_shardings(mesh)
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    from repro.launch.sharding import norm_path

    by_path = {norm_path(p): s.spec for p, s in flat}
    # spot-check rule hits (axis size 1 keeps divisibility => names kept)
    assert by_path["layers/moe/gate"][1] == "model"       # EP on experts
    assert by_path["embed"][0] == "model"                 # vocab sharded
    assert by_path["layers/attn/wkv_b"][2] == "model"     # MLA up-proj TP


# --------------------------------------------------------------------- #
# persistent compile cache placement
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_directory_is_fixed(env_set, tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and the helper sets nothing;
    unset, the cache goes to ``<repo>/.jax_cache`` — never a temp,
    pid- or time-derived path."""
    import pathlib

    from repro.launch import compile_cache as cc

    repo = pathlib.Path(__file__).resolve().parents[1]
    assert cc.REPO_CACHE_DIR == repo / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        cache = cc.enable_compile_cache()
        want = str(tmp_path) if env_set else str(cc.REPO_CACHE_DIR)
        assert cache.directory == want
        assert jax.config.jax_compilation_cache_dir == (
            before if env_set else want)
        assert (cache.hits, cache.writes) == (0, 0)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
