"""Distributed runtime: rules, straggler mitigation, elastic planning,
and (in a subprocess with forced host devices) a real sharded train
step + elastic re-shard on a debug mesh."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.rules import adjust_batch_rule, batch_axis_for, make_rules
from repro.distributed.elastic import grad_accum_factor, plan_mesh
from repro.distributed.straggler import StragglerConfig, StragglerTracker


# ------------------------------------------------------------------ rules
def test_rules_heads_mode():
    r = make_rules(get_config("qwen3_8b"))           # 32 heads % 16 == 0
    assert r["q_heads"] == "model" and r["head_dim"] is None


def test_rules_dim_mode_for_odd_heads():
    r = make_rules(get_config("yi_34b"))             # 56 heads, dh=128
    assert r["q_heads"] is None and r["head_dim"] == "model"


def test_rules_decode_mode_shards_head_dim():
    r = make_rules(get_config("command_r_35b"), job="decode")
    assert r["head_dim"] == "model" and r["kv_heads"] is None


def test_rules_ep_for_granite_moe():
    r = make_rules(get_config("granite_moe_1b_a400m"))
    assert r["expert"] == "model"
    r2 = make_rules(get_config("mixtral_8x22b"))
    assert r2["expert"] is None and r2["ff"] == "model"


def test_batch_axis_shrinks_for_tiny_batch():
    assert batch_axis_for(256, False) == "data"
    assert batch_axis_for(1, False) is None
    assert batch_axis_for(256, True) == ("pod", "data")
    assert batch_axis_for(2, True) == "pod"


# -------------------------------------------------------------- straggler
def test_straggler_detection_and_reassignment():
    tr = StragglerTracker(4, StragglerConfig(min_samples=4, k_dev=2.0))
    for step in range(10):
        for w in range(4):
            tr.observe(w, 1.0 if w != 3 else 3.0)
    assert tr.stragglers() == [3]
    mb = {0: [0, 1], 1: [2, 3], 2: [4, 5], 3: [6, 7]}
    out = tr.reassign(mb)
    assert len(out[3]) == 1                       # shed load
    total = sorted(sum(out.values(), []))
    assert total == list(range(8))                # batch preserved


def test_straggler_eviction_streak():
    cfg = StragglerConfig(min_samples=2, k_dev=1.5, evict_after=3)
    tr = StragglerTracker(2, cfg)
    for _ in range(10):
        tr.observe(0, 1.0)
        tr.observe(1, 5.0)
        tr.stragglers()
    assert tr.to_evict() == [1]


# ---------------------------------------------------------------- elastic
def test_plan_mesh_degrades_gracefully():
    assert plan_mesh(512).n_devices == 512
    assert plan_mesh(511).n_devices == 256
    p = plan_mesh(100)
    assert p.n_devices <= 100
    assert plan_mesh(1).n_devices == 1
    with pytest.raises(RuntimeError):
        plan_mesh(0)


def test_grad_accum_keeps_global_batch():
    assert grad_accum_factor(256, 16, 8, 2) == 16
    assert grad_accum_factor(256, 16, 16, 2) == 8


# ------------------------------------------------- subprocess integration
_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, json
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.distributed.rules import make_rules, adjust_batch_rule
    from repro.distributed.sharding import use_rules, param_specs
    from repro.launch.mesh import make_debug_mesh
    from repro.models.model import init_params, param_logical_axes
    from repro.optim.adamw import adamw
    from repro.training.step import init_train_state, make_train_step
    from repro.distributed.elastic import plan_mesh, reshard_state
    from jax.sharding import PartitionSpec as P

    cfg = get_smoke_config("qwen3_8b")
    mesh = make_debug_mesh((2, 4), ("data", "model"))
    rules = {**make_rules(cfg, model_axis=4), "batch": "data"}
    # smoke dims: 4 heads % 4 == 0 -> heads mode on the debug mesh
    opt = adamw(1e-3)

    # in_shardings as NamedSharding trees over the explicit mesh.
    # P is a tuple subclass -> needs is_leaf.
    from jax.sharding import NamedSharding as NS
    def shard_tree(tree, m):
        return jax.tree.map(lambda s: NS(m, s), tree,
                            is_leaf=lambda x: isinstance(x, P))

    with jax.set_mesh(mesh), use_rules(rules):
        state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
        p_specs = param_specs(param_logical_axes(cfg), rules)
        specs = {
            "params": p_specs,
            "opt_state": {"mu": p_specs, "nu": p_specs, "step": P()},
            "step": P(),
        }
        specs = shard_tree(specs, mesh)
        # place concrete arrays on the mesh per the specs (jit
        # in_shardings must match committed array shardings)
        la = param_logical_axes(cfg)
        state = {
            "params": reshard_state(state["params"], la, mesh, rules),
            "opt_state": {
                "mu": reshard_state(state["opt_state"]["mu"], la, mesh, rules),
                "nu": reshard_state(state["opt_state"]["nu"], la, mesh, rules),
                "step": state["opt_state"]["step"],
            },
            "step": state["step"],
        }
        batch_specs = shard_tree({"tokens": P("data", None),
                                  "targets": P("data", None)}, mesh)
        step = jax.jit(make_train_step(cfg, opt),
                       in_shardings=(specs, batch_specs),
                       out_shardings=(specs, NS(mesh, P())))
        from jax.sharding import NamedSharding
        toks = jax.device_put(
            jnp.zeros((4, 32), jnp.int32) + 3,
            NamedSharding(mesh, P("data", None)))
        batch = {"tokens": toks, "targets": toks}
        state, metrics = step(state, batch)
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss)

        # --- elastic: shrink to 4 devices, re-shard, keep training ---
        plan = plan_mesh(4)
        assert plan.n_devices <= 4
        mesh2 = make_debug_mesh((2, 2), ("data", "model"))
        rules2 = {**make_rules(cfg, model_axis=2), "batch": "data"}
    with jax.set_mesh(mesh2), use_rules(rules2):
        from jax.sharding import NamedSharding as NS
        rep2 = NS(mesh2, P())
        state2 = {
            "params": reshard_state(
                state["params"], param_logical_axes(cfg), mesh2, rules2),
            "opt_state": {
                "mu": reshard_state(state["opt_state"]["mu"],
                                    param_logical_axes(cfg), mesh2, rules2),
                "nu": reshard_state(state["opt_state"]["nu"],
                                    param_logical_axes(cfg), mesh2, rules2),
                "step": jax.device_put(state["opt_state"]["step"], rep2),
            },
            "step": jax.device_put(state["step"], rep2),
        }
        from jax.sharding import NamedSharding
        toks2 = jax.device_put(
            jnp.zeros((4, 32), jnp.int32) + 3,
            NamedSharding(mesh2, P("data", None)))
        batch2 = {"tokens": toks2, "targets": toks2}
        step2 = jax.jit(make_train_step(cfg, opt))
        state2, metrics2 = step2(state2, batch2)
        loss2 = float(metrics2["loss"])
        assert np.isfinite(loss2)
    print(json.dumps({"loss": loss, "loss2": loss2}))
""")


@pytest.mark.slow
def test_sharded_train_step_and_elastic_reshard():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    # forced host devices: the child must never reach for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert np.isfinite(res["loss"]) and np.isfinite(res["loss2"])
