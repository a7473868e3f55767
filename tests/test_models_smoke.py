"""Per-arch smoke tests: reduced config, one forward/train step on CPU,
asserting output shapes and no NaNs (per the brief)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_reduced_config
from repro.models import (
    SHAPES,
    active_param_count,
    build,
    init_split,
    param_count,
    supports_shape,
)


def _batch_for(cfg, key, b=2, s=16):
    ks = jax.random.split(key, 4)
    batch = {
        "tokens": jax.random.randint(ks[0], (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (b, s), 0, cfg.vocab_size),
    }
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(ks[2], (b, s, cfg.d_model))
    if cfg.num_patches:
        batch["patch_embeds"] = jax.random.normal(
            ks[3], (b, cfg.num_patches, cfg.patch_embed_dim)
        )
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_loss(arch):
    cfg = get_reduced_config(arch)
    api = build(cfg)
    values, axes = init_split(cfg, jax.random.PRNGKey(0))
    batch = _batch_for(cfg, jax.random.PRNGKey(1))
    loss, metrics = jax.jit(api.loss)(values, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"
    assert bool(jnp.isfinite(metrics["ce"]))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_train_step_grads(arch):
    """One SGD step: grads exist for every param and are finite."""
    cfg = get_reduced_config(arch)
    api = build(cfg)
    values, _ = init_split(cfg, jax.random.PRNGKey(0))
    batch = _batch_for(cfg, jax.random.PRNGKey(1))

    def loss_only(v):
        return api.loss(v, batch)[0]

    grads = jax.jit(jax.grad(loss_only))(values)
    flat, _ = jax.tree.flatten(grads)
    assert flat, "no grads"
    for g in flat:
        assert bool(jnp.all(jnp.isfinite(g))), f"{arch}: non-finite grad"
    # at least some gradient signal reaches the embedding
    leaves = {jax.tree_util.keystr(k): v for k, v in jax.tree.flatten_with_path(grads)[0]}
    emb = [v for k, v in leaves.items() if "embed" in k][0]
    assert float(jnp.abs(emb).max()) > 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_full_config_sanity(arch):
    """Full (non-reduced) configs validate and match published param counts
    to within 35% (analytic count; embeddings untied unless specified)."""
    cfg = get_config(arch)
    cfg.validate()
    expected_b = {
        "kimi-k2-1t-a32b": 1000.0,
        "qwen3-moe-30b-a3b": 30.0,
        "internlm2-20b": 20.0,
        "chatglm3-6b": 6.2,
        "llama3.2-3b": 3.2,
        "granite-3-2b": 2.6,
        "internvl2-2b": 2.0,
        "recurrentgemma-2b": 2.7,
        "whisper-tiny": 0.039,
        "mamba2-370m": 0.37,
    }[arch]
    got = param_count(cfg) / 1e9
    assert 0.65 * expected_b < got < 1.6 * expected_b, (arch, got, expected_b)
    assert active_param_count(cfg) <= param_count(cfg)


def test_moe_active_params():
    cfg = get_config("kimi-k2-1t-a32b")
    assert 25e9 < active_param_count(cfg) < 40e9  # ~32B active


def test_shape_skip_policy():
    n_run, n_skip = 0, 0
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            ok, why = supports_shape(cfg, shape)
            n_run += ok
            n_skip += not ok
            if shape.name != "long_500k":
                assert ok
    # long_500k runs only for recurrentgemma + mamba2
    assert n_skip == 8
    assert n_run == 32


def test_stages_decomposition():
    cfg = get_config("recurrentgemma-2b")
    st = cfg.stages()
    assert st[0] == (("rglru", "rglru", "local_attn"), 8)
    assert st[1] == (("rglru", "rglru"), 1)
    assert sum(len(p) * c for p, c in st) == 26


def test_vocab_padding():
    cfg = get_config("granite-3-2b")
    assert cfg.padded_vocab % 256 == 0
    assert cfg.padded_vocab >= cfg.vocab_size
