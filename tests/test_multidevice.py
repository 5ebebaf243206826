"""Real multi-device tests (subprocess with forced host devices):
SPMD train-step equivalence, pipeline-parallel correctness, MoE
expert-parallel shard_map path, compile-only cell lowering."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_py(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_spmd_loss_matches_single_device():
    """The sharded train step computes the same loss as unsharded."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, reduce_config
        from repro.models import build_model
        from repro.models.param import init_params
        from repro.parallel.sharding import make_rules, sharding_ctx
        from repro.launch.mesh import make_mesh
        from repro.data import SyntheticTokens

        cfg = reduce_config(get_config("qwen3-1.7b"))
        model = build_model(cfg)
        params = init_params(model.param_defs(), jax.random.PRNGKey(1))
        batch = SyntheticTokens(cfg.vocab_size, 64, 8).batch(0)
        loss_ref, _ = jax.jit(model.train_loss)(params, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(cfg, mesh, "train")
        def loss_fn(p, b):
            with sharding_ctx(rules):
                return model.train_loss(p, b)
        loss_sh, _ = jax.jit(loss_fn)(params, batch)
        import numpy as np
        np.testing.assert_allclose(float(loss_ref), float(loss_sh),
                                   rtol=2e-3)
        print("SPMD-EQUIV-OK", float(loss_ref), float(loss_sh))
    """)
    assert "SPMD-EQUIV-OK" in out


def test_moe_shard_map_matches_local():
    """Expert-parallel all_to_all dispatch == single-device MoE."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduce_config
        from repro.models import build_model
        from repro.models.param import init_params
        from repro.parallel.sharding import make_rules, sharding_ctx
        from repro.launch.mesh import make_mesh
        from repro.data import SyntheticTokens

        cfg = reduce_config(get_config("qwen3-moe-30b-a3b"))
        model = build_model(cfg)
        params = init_params(model.param_defs(), jax.random.PRNGKey(2))
        batch = SyntheticTokens(cfg.vocab_size, 64, 8).batch(1)
        loss_ref, m_ref = jax.jit(model.train_loss)(params, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = make_rules(cfg, mesh, "train")
        def loss_fn(p, b):
            with sharding_ctx(rules):
                return model.train_loss(p, b)
        loss_sh, m_sh = jax.jit(loss_fn)(params, batch)
        # shard_map capacity is enforced per-shard rather than globally,
        # so a few routed tokens may differ near the capacity edge
        np.testing.assert_allclose(float(loss_ref), float(loss_sh),
                                   rtol=5e-2)
        print("MOE-EP-OK", float(loss_ref), float(loss_sh))
    """)
    assert "MOE-EP-OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.parallel.pipeline import (bubble_fraction,
                                             pipeline_forward,
                                             split_microbatches)

        S, L_per, M, mb, d = 4, 2, 8, 4, 32
        mesh = make_mesh((S,), ("stage",))
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (S, L_per, d, d)) * 0.1

        def stage_fn(wp, x):
            for i in range(L_per):
                x = jnp.tanh(x @ wp[i])
            return x

        x = jax.random.normal(jax.random.PRNGKey(1), (M * mb, d))
        xm = split_microbatches(x, M)
        f = pipeline_forward(stage_fn, mesh, S, M)
        y = jax.jit(f)(w, xm)
        # sequential reference
        ref = x
        for s in range(S):
            ref = stage_fn(w[s], ref)
        ref = split_microbatches(ref, M)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        assert abs(bubble_fraction(S, M) - 3/11) < 1e-9
        print("PIPELINE-OK")
    """)
    assert "PIPELINE-OK" in out


def test_dryrun_cell_compiles_small_mesh():
    """A compile-only cell (``launch.specs.build_cell``) lowered,
    compiled and read by ``launch.roofline.analyze`` on an 8-device
    mesh."""
    out = run_py("""
        import jax, dataclasses
        from repro.configs import get_config, SHAPES
        from repro.launch.mesh import make_mesh
        from repro.launch.specs import build_cell
        from repro.launch.roofline import analyze

        cfg = get_config("granite-3-2b", n_layers=4)
        mesh = make_mesh((2, 4), ("data", "model"))
        for shape in ("train_4k", "decode_32k"):
            cell = build_cell(cfg, SHAPES[shape], mesh)
            compiled = cell.lower().compile()
            rep = analyze(cell, compiled, mesh_name="test8")
            assert rep.flops > 0 and rep.hbm_bytes > 0
            assert compiled.memory_analysis().temp_size_in_bytes > 0
        print("DRYRUN-CELL-OK")
    """)
    assert "DRYRUN-CELL-OK" in out


def test_elastic_checkpoint_across_device_counts(tmp_path):
    """Save sharded on 8 devices -> restore on 1 (elastic rescale)."""
    d = str(tmp_path)
    run_py(f"""
        import jax
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_config, reduce_config
        from repro.models import build_model
        from repro.train import init_train_state
        from repro.train.train_step import TrainHParams
        cfg = reduce_config(get_config("granite-3-2b"))
        model = build_model(cfg)
        state = init_train_state(model, jax.random.PRNGKey(0),
                                 TrainHParams())
        CheckpointManager({d!r}).save(5, state, {{"mesh": "2x4"}})
        print("SAVED")
    """, devices=8)
    out = run_py(f"""
        import jax
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_config, reduce_config
        from repro.models import build_model
        from repro.train import init_train_state
        from repro.train.train_step import TrainHParams
        cfg = reduce_config(get_config("granite-3-2b"))
        model = build_model(cfg)
        state = init_train_state(model, jax.random.PRNGKey(1),
                                 TrainHParams())
        restored, meta = CheckpointManager({d!r}).restore(state)
        assert meta["mesh"] == "2x4" and int(restored.step) == 0
        print("ELASTIC-OK", len(jax.tree.leaves(restored)))
    """, devices=1)
    assert "ELASTIC-OK" in out
