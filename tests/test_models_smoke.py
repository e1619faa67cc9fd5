"""Per-architecture smoke tests (deliverable f): reduced same-family
variant, one forward + one train step on CPU; asserts shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import f32_cfg, make_batch
from repro.configs import ASSIGNED_ARCHS, get_smoke_config
from repro.models.api import build_model
from repro.optim import adamw_init, adamw_update
from repro.optim.adamw import AdamWConfig

ALL = ASSIGNED_ARCHS + ["elasticbert12", "granite-4.0-h-micro"]


@pytest.mark.parametrize("arch", ALL)
def test_smoke_forward_and_train_step(arch):
    cfg = f32_cfg(get_smoke_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, b=2, s=16)

    loss_fn = jax.jit(lambda p, b: model.train_loss(p, b, remat=False))
    loss, grads = jax.value_and_grad(
        lambda p: model.train_loss(p, batch, remat=False))(params)
    assert np.isfinite(float(loss)), arch
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat), arch

    opt = adamw_init(params)
    new_params, _, gnorm = adamw_update(params, grads, opt, AdamWConfig())
    assert np.isfinite(float(gnorm))
    # one optimizer step must change parameters
    changed = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert changed


@pytest.mark.parametrize("arch", [a for a in ALL
                                  if a != "seamless-m4t-large-v2"])
def test_smoke_exit_observables(arch):
    cfg = f32_cfg(get_smoke_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, b=2, s=16, with_labels=False)
    out = model.forward_exits(params, batch)
    L, B = cfg.num_layers, 2
    assert out["conf"].shape == (L, B)
    assert out["pred"].shape == (L, B)
    conf = np.asarray(out["conf"])
    assert np.isfinite(conf).all() and (conf > 0).all() and (conf <= 1).all()
    out_dim = cfg.num_classes or cfg.vocab_size
    assert (np.asarray(out["pred"]) < out_dim).all()


@pytest.mark.parametrize("arch", ALL)
def test_smoke_decode_step(arch):
    cfg = f32_cfg(get_smoke_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    caches = model.init_caches(B, S)
    extras = None
    if model.is_encdec:
        from repro.models import encdec
        frames = jax.random.normal(jax.random.PRNGKey(1),
                                   (B, cfg.encoder.source_len,
                                    cfg.encoder.d_model))
        enc_out = encdec.encode(params, cfg, frames)
        extras = {"cross_kv": encdec.cross_kv(params, cfg, enc_out)}
    if cfg.modality == "vision_stub":
        tok = jax.random.normal(jax.random.PRNGKey(2), (B, 1, cfg.d_model))
    else:
        tok = jnp.zeros((B,), jnp.int32)
    logits, conf, pred, new_caches = model.decode_step(
        params, caches, tok, jnp.int32(0), extras=extras,
        split_layer=cfg.num_layers // 2, window_seq_len=S)
    out_dim = cfg.num_classes or cfg.vocab_size
    assert logits.shape == (B, out_dim)
    assert np.isfinite(np.asarray(logits)).all()
    assert conf.shape == (B,)
    assert np.isfinite(np.asarray(conf)).all()
    # cache structure preserved
    assert jax.tree.structure(caches) == jax.tree.structure(new_caches)
