"""The plain reference against the program at a tiny size on the CPU: the
model's own dense forward, the engine's prefill-then-decode through
``generate()``, and the precision control, which has to fail."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common
from chipbench.references import mistral as ref

SIZES = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
         "max_position_embeddings": 128, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
         "sliding_window": 16}


def program_logits(params, ids):
    from deepspeed_tpu.models import mistral
    cfg = mistral.MistralConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                                num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
                                rms_eps=1e-5, sliding_window=16, remat=False)
    with jax.default_matmul_precision("highest"):
        return mistral.forward(cfg, params, jnp.asarray([ids]),
                               attention_fn=mistral.dense_windowed_attention(16))[0]


def test_reference_imports_nothing_of_the_programs_models():
    import inspect
    assert "deepspeed_tpu" not in inspect.getsource(ref).split('"""', 2)[2]


def test_reference_equals_the_programs_dense_forward_past_the_window():
    params = jax.jit(lambda k: ref.init_params(SIZES, k, jnp.float32))(jax.random.PRNGKey(3))
    ids = np.random.default_rng(0).integers(0, 256, 50).tolist()  # 50 > window 16
    want = np.asarray(program_logits(params, ids))
    got = np.asarray(ref.logits_rows(SIZES, params, ids, list(range(50))))
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
    # blocks of queries change nothing
    small = np.asarray(jax.jit(lambda p, x: ref.hidden_states(SIZES, p, x))(params, jnp.asarray(ids)))
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (50, h, 16)) for i, h in ((0, 4), (1, 2), (2, 2)))
    assert np.allclose(ref.attention(q, k, v, 16, q_block=8), ref.attention(q, k, v, 16, q_block=64),
                       atol=1e-5)
    assert np.isfinite(small).all()


def test_padding_after_the_last_row_changes_nothing():
    params = jax.jit(lambda k: ref.init_params(SIZES, k, jnp.float32))(jax.random.PRNGKey(4))
    ids = np.random.default_rng(1).integers(0, 256, 20).tolist()
    a = np.asarray(ref.logits_rows(SIZES, params, ids, [5, 19]))
    b = np.asarray(ref.logits_rows(SIZES, params, ids + [0] * 12, [5, 19]))
    assert np.allclose(a, b, atol=1e-5)


def test_loss_and_gradient_equal_the_programs():
    from deepspeed_tpu.models import mistral
    params = jax.jit(lambda k: ref.init_params(SIZES, k, jnp.float32))(jax.random.PRNGKey(5))
    ids = np.random.default_rng(2).integers(0, 256, (2, 24)).astype(np.int32)
    cfg = mistral.MistralConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                                num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=24,
                                rms_eps=1e-5, sliding_window=16, remat=False)
    labels = np.full_like(ids, -100)
    labels[:, :-1] = ids[:, 1:]
    loss_fn = mistral.make_loss_fn(cfg, attention_fn=mistral.dense_windowed_attention(16))
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(
            lambda p: loss_fn(p, {"input_ids": ids, "labels": labels}, None))(params)
    got, grads = jax.jit(lambda p, x: ref.loss_and_grads(SIZES, p, x))(params, ids)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(ref.global_norm(grads)) == pytest.approx(float(ref.global_norm(want_grads)), rel=1e-4)
    # a descent step descends all of the gradient's mass; its opposite, and no step, none
    down = jax.tree_util.tree_map(lambda g: -jnp.sign(g).astype(jnp.int8), grads)
    up = jax.tree_util.tree_map(lambda g: jnp.sign(g).astype(jnp.int8), grads)
    assert float(ref.not_descended_share(grads, down)) == 0.0
    assert float(ref.not_descended_share(grads, up)) == pytest.approx(1.0)
    still = jax.tree_util.tree_map(lambda g: jnp.zeros(g.shape, jnp.int8), grads)
    assert float(ref.not_descended_share(grads, still)) == pytest.approx(1.0)


def test_engine_agrees_and_the_fp8_control_does_not(rehearse):
    """Prefill-then-decode through generate() at the rehearsal size: inside
    the limits as built, outside them with the weights rounded through fp8."""
    sound = rehearse("--workload", "serve.chat-burst", "--seed", "11", "--seconds", "0")
    control = rehearse("--workload", "serve.chat-burst", "--seed", "11", "--seconds", "0",
                       "--control", "1")
    limit = common.load_json("configs", "mistral-7b-serve-16l.json")["correct"]["logit_rel_rms_limit"]
    assert sound.line["would_be_correct"] is True
    assert control.line["would_be_correct"] is False
    assert sound.number("logit_rel_rms") < limit < control.number("logit_rel_rms")
    assert control.number("logit_rel_rms") > 3 * sound.number("logit_rel_rms")


def test_train_step_agrees_and_the_fp8_control_does_not(rehearse):
    sound = rehearse("--workload", "train.zero3-fsdp4", "--seed", "12", "--seconds", "0")
    control = rehearse("--workload", "train.zero3-fsdp4", "--seed", "12", "--seconds", "0",
                       "--control", "1")
    assert sound.line["would_be_correct"] is True and control.line["would_be_correct"] is False
    # at this size loss and norm are a sanity bound of the rehearsal's own (the
    # chip's limits, read at the cell's size, are tighter); the share decides
    spec = common.load_json("configs", "mistral-7b-zero3-fsdp4.json")
    chip, tiny = spec["correct"], common.correct_limits(spec, rehearse=True)
    assert chip["loss_rel_limit"] < tiny["loss_rel_limit"] == 0.002
    assert chip["grad_norm_rel_limit"] < tiny["grad_norm_rel_limit"] == 0.02
    assert common.correct_limits(spec, rehearse=False) == chip
    assert sound.number("loss_rel_err") < 0.002 and sound.number("grad_norm_rel_err") < 0.02
    assert (control.number("not_descended_share") > tiny["not_descended_share_limit"]
            > 5 * sound.number("not_descended_share"))
