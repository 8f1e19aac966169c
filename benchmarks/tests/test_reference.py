"""``benchmarks/reference.py`` against the program's model at a tiny
size in float32 on the CPU: logits and gradients; and the FLOP count
against a count made by hand."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops, modelcfg, reference

TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "rope_theta": 1000000.0,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-05,
    "sliding_window": None, "tie_word_embeddings": False,
}


@pytest.fixture(scope="module")
def model():
    import dataclasses

    from ray_tpu.models.llama import init_params

    cfg = dataclasses.replace(
        modelcfg.llama_config(TINY, remat="none"), dtype=jnp.float32
    )
    params = init_params(jax.random.key(0), cfg)
    # Norm scales start at 0 (weight 1): move them, or the reference's
    # reading of the weight as 1 + scale is not exercised.
    keys = jax.random.split(jax.random.key(1), 3)
    params["blocks"]["attn_norm"] = 0.1 * jax.random.normal(keys[0], (2, 64))
    params["blocks"]["mlp_norm"] = 0.1 * jax.random.normal(keys[1], (2, 64))
    params["final_norm"] = 0.1 * jax.random.normal(keys[2], (64,))
    tokens = jax.random.randint(jax.random.key(2), (2, 33), 0, 512)
    return cfg, params, tokens


def test_logits_match_models_forward(model):
    from ray_tpu.models.llama import forward

    cfg, params, tokens = model
    want = reference.forward(params, tokens[:, :-1], **reference.for_model(TINY))
    got = forward(params, tokens[:, :-1], cfg)
    # Both float32; the orders of reduction differ.
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_loss_and_gradients_match_the_train_steps_loss(model):
    from ray_tpu.train.step import loss_fn

    cfg, params, tokens = model
    kw = reference.for_model(TINY)
    want_loss, want = jax.value_and_grad(reference.loss)(params, tokens, **kw)
    (got_loss, _), got = jax.value_and_grad(loss_fn, has_aux=True)(
        params, {"tokens": tokens}, cfg
    )
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-5)
    flat_want = jax.tree.leaves_with_path(want)
    flat_got = dict(jax.tree.leaves_with_path(got))
    for path, w in flat_want:
        np.testing.assert_allclose(flat_got[path], w, atol=3e-6, rtol=1e-4,
                                   err_msg=str(path))


def test_a_file_cannot_state_what_the_program_does_not_run():
    with pytest.raises(ValueError, match="head_dim"):
        modelcfg.llama_config({**TINY, "head_dim": 32})
    with pytest.raises(ValueError, match="eps"):
        modelcfg.llama_config({**TINY, "rms_norm_eps": 1e-6})
    with pytest.raises(ValueError, match="sliding"):
        modelcfg.llama_config({**TINY, "sliding_window": 4096})


def test_flops_by_hand():
    d, f, v, layers, s = 64, 128, 512, 2, 32
    per_layer = d * 64 + 2 * d * 32 + 64 * d + 3 * d * f
    n = layers * per_layer + d * v
    assert flops.matmul_params(TINY) == n
    attention = layers * 4 * 64 * (s + 1) / 2
    assert flops.forward_flops_per_token(TINY, s) == 2 * n + attention
    assert flops.train_flops_per_token(TINY, s) == 3 * (2 * n + attention)
    # Mistral-7B-v0.3 whole: 7.25 B parameters.
    full = {**TINY, "hidden_size": 4096, "intermediate_size": 14336,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "head_dim": 128, "num_hidden_layers": 32, "vocab_size": 32768}
    assert flops.total_params(full) == pytest.approx(7.248e9, rel=1e-3)


def test_unknown_device_kind_is_an_error():
    from benchmarks import peaks

    assert peaks.load("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownDeviceKind):
        peaks.load("TPU v9")
