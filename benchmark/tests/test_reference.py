"""The plain reference agrees with the program's model at a tiny size (on
the chip the runners compare them at the published widths)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dense_decoder

TINY = dict(n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab_size=256,
            max_seq_len=96)
FAMILIES = {"gpt2": ("medium", "gelu_new"), "opt": ("1.3b", "relu")}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_reference_matches_the_programs_model(family):
    from deepspeed_tpu.models import get_model
    from deepspeed_tpu.models.layers import split_params_axes

    size, activation = FAMILIES[family]
    model = get_model(family, size, compute_dtype=jnp.float32, **TINY)
    params = split_params_axes(model.init(jax.random.PRNGKey(3)))[0]
    # biases and norms start at 0 and 1: perturb them so that a dropped
    # bias or scale would show
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape), params)
    arch = dict(TINY, activation=activation, layernorm_eps=1e-5)
    ids = np.random.default_rng(0).integers(0, 256, (2, 96), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply(params, jnp.asarray(ids)))
        want_loss = float(model.loss(params, {"input_ids": jnp.asarray(ids)}))
    got = np.stack([np.asarray(dense_decoder.logits_at(
        params, ids[i:i + 1], arch, 0, 96)) for i in range(2)])
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    assert dense_decoder.loss(params, ids, arch) == pytest.approx(
        want_loss, abs=1e-4)


def test_blocked_attention_equals_whole(monkeypatch):
    """Sequences longer than one query block give the same logits."""
    from deepspeed_tpu.models import get_model
    from deepspeed_tpu.models.layers import split_params_axes

    model = get_model("opt", "1.3b", compute_dtype=jnp.float32, **TINY)
    params = split_params_axes(model.init(jax.random.PRNGKey(5)))[0]
    arch = dict(TINY, activation="relu", layernorm_eps=1e-5)
    ids = np.random.default_rng(1).integers(0, 256, (1, 96), dtype=np.int32)
    whole = np.asarray(dense_decoder.logits_at(params, ids, arch, 10, 40))
    monkeypatch.setattr(dense_decoder, "Q_BLOCK", 32)
    dense_decoder.block.clear_cache()
    blocked = np.asarray(dense_decoder.logits_at(params, ids, arch, 10, 40))
    dense_decoder.block.clear_cache()
    np.testing.assert_allclose(blocked, whole, atol=1e-5, rtol=1e-5)
