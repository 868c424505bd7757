"""Distribution-layer tests that need >1 device: run in a subprocess with
XLA_FLAGS so they don't disturb this process's 1-device jax.

Covers: sharded-MoE == local oracle (incl. non-divisible expert counts),
sharding rule derivation, mesh construction, and a mini dry-run
(lower+compile of a reduced arch on an 8-device mesh).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code],
                       env={**os.environ, "PYTHONPATH": SRC},
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    return r.stdout


def test_moe_sharded_matches_local_oracle():
    _run("""
        import repro.sharding.rules as R
        from repro.sharding import AxisRules, set_rules
        from repro.models.lm.config import LMConfig
        from repro.models.lm.moe import _moe_local, moe_block
        from repro.models.lm.model import _moe_params
        R.AXIS_SIZES.update({"data": 2, "model": 4})
        set_rules(AxisRules(batch_axes=("data",), model_axis_size=4))
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        for ne in (8, 6):   # divisible and padded expert counts
            cfg = LMConfig(name="t", arch_type="moe", num_layers=1,
                           d_model=32, num_heads=4, num_kv_heads=2, d_ff=0,
                           vocab_size=64, num_experts=ne, experts_per_tok=2,
                           moe_d_ff=16, dtype="float32")
            p = _moe_params(cfg, jax.random.key(0), jnp.float32)
            x = jax.random.normal(jax.random.key(1), (4, 8, 32))
            ref, _ = jax.jit(lambda p, x: _moe_local(p, x, cfg))(p, x)
            with mesh:
                out, _ = jax.jit(lambda p, x: moe_block(p, x, cfg))(p, x)
            err = float(jnp.abs(ref - out).max())
            assert err < 1e-4, (ne, err)
            def loss(p, x):
                with mesh:
                    o, a = moe_block(p, x, cfg)
                return (o ** 2).sum() + a
            g = jax.jit(jax.grad(loss))(p, x)
            assert all(np.isfinite(np.asarray(l)).all()
                       for l in jax.tree.leaves(g))
        print("OK")
    """)


def test_mini_dryrun_lowers_on_mesh():
    _run("""
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro.sharding.rules as R
        from repro.sharding import AxisRules, set_rules, param_pspecs
        from repro.configs import get_config, smoke_variant
        from repro.models.lm import abstract_params, make_train_step
        from repro.optim.optimizers import AdamWState
        R.AXIS_SIZES.update({"data": 2, "model": 4})
        set_rules(AxisRules(batch_axes=("data",), model_axis_size=4))
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = dataclasses.replace(smoke_variant(get_config("llama3-8b")),
                                  num_layers=2, remat=True)
        params_abs = abstract_params(cfg)
        ps = param_pspecs(params_abs, fsdp=True)
        sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        psh = sh(ps)
        osh = AdamWState(step=NamedSharding(mesh, P()), mu=psh, nu=psh)
        opt_abs = AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape,
                                                           jnp.float32),
                            params_abs),
            nu=jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape,
                                                           jnp.float32),
                            params_abs))
        batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32)}
        bsh = {"tokens": NamedSharding(mesh, P("data", None))}
        with mesh:
            c = jax.jit(make_train_step(cfg),
                        in_shardings=(psh, osh, bsh),
                        out_shardings=(psh, osh, None)).lower(
                            params_abs, opt_abs, batch).compile()
        assert c.cost_analysis() is not None
        print("OK", c.memory_analysis().temp_size_in_bytes)
    """)


def test_sharded_train_step_matches_single_device():
    """Numerical equivalence: one train step on the mesh == on one device."""
    _run("""
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro.sharding.rules as R
        from repro.sharding import AxisRules, set_rules, param_pspecs
        from repro.configs import get_config, smoke_variant
        from repro.models.lm import init_train_state, make_train_step
        R.AXIS_SIZES.update({"data": 2, "model": 4})
        set_rules(AxisRules(batch_axes=("data",), model_axis_size=4))
        cfg = dataclasses.replace(smoke_variant(get_config("qwen3-8b")),
                                  num_layers=2)
        step = make_train_step(cfg, lr=1e-3)
        params, opt = init_train_state(cfg, seed=0)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 64)))}
        p1, _, m1 = jax.jit(step)(params, opt, batch)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ps = param_pspecs(params, fsdp=False)
        sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        with mesh:
            p2, _, m2 = jax.jit(step, in_shardings=(sh(ps), None, None),
                                out_shardings=(sh(ps), None, None))(
                                    params, opt, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (
            float(m1["loss"]), float(m2["loss"]))
        d = max(float(jnp.abs(a - b).max())
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert d < 5e-2, d
        print("OK", float(m1["loss"]), float(m2["loss"]))
    """)


def test_moe_a2a_dispatch_matches_local_oracle():
    _run("""
        import dataclasses
        import repro.sharding.rules as R
        from repro.sharding import AxisRules, set_rules
        from repro.models.lm.config import LMConfig
        from repro.models.lm.moe import _moe_local, moe_block
        from repro.models.lm.model import _moe_params
        R.AXIS_SIZES.update({"data": 2, "model": 4})
        set_rules(AxisRules(batch_axes=("data",), model_axis_size=4))
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        for ne in (8, 6):
            cfg = LMConfig(name="t", arch_type="moe", num_layers=1,
                           d_model=32, num_heads=4, num_kv_heads=2, d_ff=0,
                           vocab_size=64, num_experts=ne, experts_per_tok=2,
                           moe_d_ff=16, dtype="float32",
                           moe_dispatch="a2a", moe_capacity_factor=8.0)
            p = _moe_params(cfg, jax.random.key(0), jnp.float32)
            x = jax.random.normal(jax.random.key(1), (4, 8, 32))
            ref, _ = jax.jit(lambda p, x: _moe_local(p, x, cfg))(p, x)
            with mesh:
                out, _ = jax.jit(lambda p, x: moe_block(p, x, cfg))(p, x)
            err = float(jnp.abs(ref - out).max())
            assert err < 1e-4, (ne, err)
        print("OK")
    """)


def test_pure_fsdp_mode_lowers():
    _run("""
        import dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro.sharding.rules as R
        from repro.sharding import AxisRules, set_rules, param_pspecs
        from repro.configs import get_config, smoke_variant
        from repro.models.lm import abstract_params, make_train_step
        from repro.optim.optimizers import AdamWState
        R.AXIS_SIZES.update({"data": 2, "model": 4})
        set_rules(AxisRules(batch_axes=("data", "model"), fsdp_axis=None,
                            seq_shard_activations=False, pure_fsdp=True,
                            model_axis_size=4))
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = dataclasses.replace(smoke_variant(get_config("llama3-8b")),
                                  num_layers=2, d_model=256)
        params_abs = abstract_params(cfg)
        ps = param_pspecs(params_abs)
        sh = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        psh = sh(ps)
        osh = AdamWState(step=NamedSharding(mesh, P()), mu=psh, nu=psh)
        opt_abs = AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape,
                                                           jnp.float32),
                            params_abs),
            nu=jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape,
                                                           jnp.float32),
                            params_abs))
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        bsh = {"tokens": NamedSharding(mesh, P(("data", "model"), None))}
        with mesh:
            c = jax.jit(make_train_step(cfg),
                        in_shardings=(psh, osh, bsh),
                        out_shardings=(psh, osh, None)).lower(
                            params_abs, opt_abs, batch).compile()
        print("OK", c.memory_analysis().temp_size_in_bytes)
    """)


def test_production_mesh_shapes():
    _run("""
        import os
        from repro.launch.mesh import make_production_mesh
        # 8 placeholder devices can't build 256; just validate the axis spec
        try:
            make_production_mesh()
        except Exception as e:
            assert "256" in str(e) or "devices" in str(e).lower()
        print("OK")
    """)
