import dataclasses
import os

# Tier-1 runs on the CPU: a chip belongs to one process at a time and the
# suite runs in several workers, so no test may take an attached TPU.
# Set before jax is imported; an explicit JAX_PLATFORMS still wins.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

# Smoke tests and benches must see ONE host device; the multi-device
# tests force more in their own subprocesses. This reads the environment
# only (no backend is initialised here), so every worker collects alike.
if "xla_force_host_platform_device_count" in os.environ.get("XLA_FLAGS", ""):
    raise pytest.UsageError(
        "tier-1 expects one host device: unset "
        "--xla_force_host_platform_device_count in XLA_FLAGS (the "
        "multi-device tests set it for their own subprocesses)")


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)


def make_batch(cfg, b=2, s=16, seed=0, with_labels=True):
    """Random batch matching a ModelConfig's modality."""
    import jax.numpy as jnp
    key = jax.random.PRNGKey(seed)
    batch = {}
    if cfg.modality == "vision_stub":
        batch["embeds"] = jax.random.normal(key, (b, s, cfg.d_model),
                                            jnp.float32)
    elif cfg.modality == "audio_stub":
        batch["frames"] = jax.random.normal(
            key, (b, cfg.encoder.source_len, cfg.encoder.d_model),
            jnp.float32)
        batch["tokens"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    else:
        batch["tokens"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    if with_labels:
        if cfg.num_classes:
            batch["labels"] = jax.random.randint(key, (b,), 0,
                                                 cfg.num_classes)
        else:
            batch["labels"] = jax.random.randint(key, (b, s), 0,
                                                 cfg.vocab_size)
    return batch


def f32_cfg(cfg):
    return dataclasses.replace(cfg, dtype="float32")
