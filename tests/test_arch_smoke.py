"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs.  The FULL
configs are exercised only by lowering against ShapeDtypeStructs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, reduce_config
from repro.models import build_model
from repro.models.param import init_params


def _batch_for(cfg, b=2, s=64):
    key = jax.random.PRNGKey(0)
    tok = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tok, "labels": tok}
    if cfg.vlm is not None:
        n_p = cfg.vlm.n_patches
        batch["tokens"] = tok[:, : s - n_p]
        batch["labels"] = tok[:, : s - n_p]
        batch["patch_embeds"] = jnp.ones((b, n_p, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jnp.ones((b, cfg.encdec.enc_len, cfg.d_model),
                                   jnp.float32)
    return batch


@pytest.fixture(scope="module")
def arch_setup():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = reduce_config(get_config(arch))
            model = build_model(cfg)
            params = init_params(model.param_defs(), jax.random.PRNGKey(1))
            cache[arch] = (cfg, model, params)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step_no_nans(arch, arch_setup):
    cfg, model, params = arch_setup(arch)
    batch = _batch_for(cfg)
    loss, metrics = jax.jit(model.train_loss)(params, batch)
    assert jnp.isfinite(loss), (arch, loss)
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_prefill_decode_shapes(arch, arch_setup):
    cfg, model, params = arch_setup(arch)
    batch = _batch_for(cfg)
    if cfg.family == "encdec":
        inputs = {"frames": batch["frames"], "tokens": batch["tokens"]}
    else:
        inputs = {k: batch[k] for k in ("tokens", "patch_embeds")
                  if k in batch}
    logits, cache = jax.jit(
        lambda p, i: model.prefill(p, i, max_len=96))(params, inputs)
    vp = -(-cfg.vocab_size // 2048) * 2048
    assert logits.shape == (2, 1, vp)
    assert jnp.isfinite(logits).all()
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    logits2, cache = jax.jit(model.decode_step)(params, cache, tok)
    assert logits2.shape == (2, 1, vp)
    assert jnp.isfinite(logits2).all()
    assert int(cache["pos"]) == int(batch["tokens"].shape[1]) + \
        (cfg.vlm.n_patches if cfg.vlm is not None else 0) + 1


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "rwkv6-3b"])
def test_grad_step_updates_params(arch, arch_setup):
    from repro.train import init_train_state, make_train_step
    from repro.train.train_step import TrainHParams

    cfg, model, _ = arch_setup(arch)
    hp = TrainHParams(total_steps=4, warmup=1)
    state = init_train_state(model, jax.random.PRNGKey(0), hp)
    step = jax.jit(make_train_step(model, hp))
    batch = _batch_for(cfg)
    new_state, metrics = step(state, batch)
    # step 0 has lr=0 under warmup; take a second step so params move
    new_state, metrics = step(new_state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert int(new_state.step) == 2
    # at least one parameter moved
    moved = jax.tree.reduce(
        lambda a, b: a or b,
        jax.tree.map(lambda a, b: bool(jnp.any(a != b)),
                     state.params, new_state.params))
    assert moved


def test_prefill_matches_decode_consistency(arch_setup):
    """Decoding t tokens one-by-one == prefilling t+prompt (same arch)."""
    cfg, model, params = arch_setup("qwen3-1.7b")
    key = jax.random.PRNGKey(7)
    tok = jax.random.randint(key, (1, 16), 0, cfg.vocab_size)
    logits_a, cache = model.prefill(params, {"tokens": tok}, max_len=32)
    # feed two more tokens via decode; compare against fresh prefill
    t1 = jnp.asarray([[11]], jnp.int32)
    t2 = jnp.asarray([[23]], jnp.int32)
    l1, cache = model.decode_step(params, cache, t1)
    l2, cache = model.decode_step(params, cache, t2)
    full = jnp.concatenate([tok, t1, t2], axis=1)
    logits_b, _ = model.prefill(params, {"tokens": full}, max_len=32)
    import numpy as np
    np.testing.assert_allclose(l2[:, -1], logits_b[:, -1], rtol=2e-2,
                               atol=2e-2)


def test_tiny_model_runs():
    from repro.models.tiny import IN_F, IN_T, TinyModel

    cfg = get_config("tiny-kws")
    model = TinyModel(cfg)
    params = init_params(model.param_defs(), jax.random.PRNGKey(0))
    x = jnp.ones((4, IN_T, IN_F))
    logits = model(params, x)
    assert logits.shape == (4, cfg.vocab_size)
    assert jnp.isfinite(logits).all()
    assert model.macs > 0 and model.sram_bytes > 0

