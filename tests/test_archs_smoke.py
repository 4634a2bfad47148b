"""Per-architecture smoke tests: reduced config, one forward/train/decode step
on CPU; assert output shapes and no NaNs.  Full widths run on the chip, in
the benchmark's cells (``bench/run.py``)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, get_config, smoke
from repro.models import model


def _batch(cfg, b=2, s=16, key=None):
    key = key or jax.random.PRNGKey(0)
    if cfg.input_mode == "tokens":
        inputs = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    else:
        inputs = jax.random.normal(key, (b, s, cfg.d_model), jnp.float32)
    targets = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    return {"inputs": inputs, "targets": targets}


@pytest.fixture(params=sorted(ARCHS))
def arch(request):
    return request.param


def test_forward_shapes_no_nan(arch):
    cfg = smoke(get_config(arch))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits, aux = model.forward(params, batch, cfg)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert not jnp.any(jnp.isnan(logits)), f"NaNs in {arch} logits"


def test_train_step_loss_finite(arch):
    cfg = smoke(get_config(arch))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(model.loss_fn, has_aux=True)(
        params, batch, cfg)
    assert jnp.isfinite(loss), f"{arch} loss not finite"
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert jnp.isfinite(gnorm), f"{arch} grad norm not finite"
    assert gnorm > 0, f"{arch} gradients are all zero"


def test_prefill_then_decode(arch):
    cfg = smoke(get_config(arch))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    b, s = 2, 16
    batch = _batch(cfg, b, s)
    caches = model.init_caches(cfg, b, max_len=32)
    logits, caches = model.prefill_step(params, batch, caches, cfg)
    assert logits.shape == (b, 1, cfg.padded_vocab)
    assert not jnp.any(jnp.isnan(logits))
    if cfg.input_mode == "tokens":
        tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], axis=-1)[:, None]
        step_in = {"inputs": tok}
    else:
        step_in = {"inputs": jax.random.normal(jax.random.PRNGKey(1), (b, 1, cfg.d_model))}
    logits2, caches = model.decode_step(params, step_in, caches, cfg)
    assert logits2.shape == (b, 1, cfg.padded_vocab)
    assert not jnp.any(jnp.isnan(logits2))


def test_decode_matches_prefill(arch):
    """Token-by-token decode must agree with a full prefill (cache correctness).

    MoE archs run with a no-drop capacity factor here: capacity dropping is
    batch-composition-dependent by construction (tested in test_models_moe),
    and would mask cache bugs with routing noise."""
    import dataclasses
    cfg = smoke(get_config(arch))
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    params = model.init_params(jax.random.PRNGKey(0), cfg)
    b, s = 1, 8
    batch = _batch(cfg, b, s)
    # full forward logits at last position
    full_logits, _ = model.forward(params, batch, cfg)
    # prefill s-1 tokens, then decode token s-1
    if cfg.input_mode == "tokens":
        pre = {"inputs": batch["inputs"][:, : s - 1]}
        last = {"inputs": batch["inputs"][:, s - 1:]}
    else:
        pre = {"inputs": batch["inputs"][:, : s - 1]}
        last = {"inputs": batch["inputs"][:, s - 1:]}
    caches = model.init_caches(cfg, b, max_len=s)
    _, caches = model.prefill_step(params, pre, caches, cfg)
    dec_logits, _ = model.decode_step(params, last, caches, cfg)
    import numpy as np
    np.testing.assert_allclose(
        np.asarray(full_logits[:, -1]), np.asarray(dec_logits[:, 0]),
        rtol=2e-2, atol=2e-2)
