"""Model family tests: shape/grad sanity, HF parity for llama/gpt2 where the
baked-in transformers lib provides reference implementations (the reference's
inference tests compare against HF outputs, tests/unit/inference/test_inference.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import bert, gpt2, llama
from deepspeed_tpu.models.transformer import cross_entropy_loss, sdpa


def test_llama_forward_shapes():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 16))
    logits = llama.forward(cfg, params, jnp.asarray(ids))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_llama_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 16))
    l1 = np.asarray(llama.forward(cfg, params, jnp.asarray(ids)))
    ids2 = ids.copy()
    ids2[0, 10] = (ids2[0, 10] + 1) % cfg.vocab_size
    l2 = np.asarray(llama.forward(cfg, params, jnp.asarray(ids2)))
    np.testing.assert_allclose(l1[0, :10], l2[0, :10], atol=1e-5)
    assert not np.allclose(l1[0, 10:], l2[0, 10:])


def test_llama_gqa_heads():
    cfg = llama.LlamaConfig.tiny(heads=4, kv_heads=2)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    assert params["layers"]["attn"]["wk"].shape[-1] == 2 * (cfg.hidden_size // 4)
    ids = np.zeros((1, 8), np.int32)
    logits = llama.forward(cfg, params, jnp.asarray(ids))
    assert np.isfinite(np.asarray(logits)).all()


def test_llama_gqa_wk_width_at_a_wide_shape():
    """The kv projection is ``KV * head_dim`` wide where heads and KV heads
    differ (20 heads over 4 KV heads of 128), by shapes alone."""
    D, F, H, KV = 2560, 6912, 20, 4
    cfg = llama.LlamaConfig(hidden_size=D, intermediate_size=F, num_heads=H,
                            num_kv_heads=KV, num_layers=2)
    p = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    assert p["layers"]["attn"]["wk"].shape == (2, D, KV * (D // H))


def test_llama_hf_parity():
    """Logit parity against transformers' LlamaForCausalLM with copied weights."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFConfig
    from transformers.models.llama.modeling_llama import LlamaForCausalLM

    cfg = llama.LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4, kv_heads=4, seq=32)
    hf_cfg = HFConfig(vocab_size=128, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4, max_position_embeddings=32,
                      rms_norm_eps=cfg.rms_eps, attention_bias=False, tie_word_embeddings=False,
                      rope_theta=cfg.rope_theta)
    hf = LlamaForCausalLM(hf_cfg).eval()

    # copy HF weights into our pytree
    sd = hf.state_dict()
    L, D = 2, 32

    def t2j(t):
        return jnp.asarray(t.detach().numpy())

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    params["embed"] = t2j(sd["model.embed_tokens.weight"])
    params["lm_head"] = t2j(sd["lm_head.weight"]).T
    params["final_norm"] = t2j(sd["model.norm.weight"])
    for field, hf_name in [("wq", "self_attn.q_proj"), ("wk", "self_attn.k_proj"),
                           ("wv", "self_attn.v_proj"), ("wo", "self_attn.o_proj")]:
        params["layers"]["attn"][field] = jnp.stack(
            [t2j(sd[f"model.layers.{i}.{hf_name}.weight"]).T for i in range(L)])
    for field, hf_name in [("w_gate", "mlp.gate_proj"), ("w_up", "mlp.up_proj"), ("w_down", "mlp.down_proj")]:
        params["layers"]["mlp"][field] = jnp.stack(
            [t2j(sd[f"model.layers.{i}.{hf_name}.weight"]).T for i in range(L)])
    params["layers"]["attn_norm"] = jnp.stack([t2j(sd[f"model.layers.{i}.input_layernorm.weight"]) for i in range(L)])
    params["layers"]["mlp_norm"] = jnp.stack(
        [t2j(sd[f"model.layers.{i}.post_attention_layernorm.weight"]) for i in range(L)])

    ids = np.random.default_rng(0).integers(0, 128, (2, 16))
    ours = np.asarray(llama.forward(cfg, params, jnp.asarray(ids)))
    with torch.no_grad():
        theirs = hf(torch.tensor(ids)).logits.numpy()
    # HF applies rotary with interleaved vs half-split convention matching ours (half-split)
    np.testing.assert_allclose(ours, theirs, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_gpt2_trains_with_engine():
    cfg = gpt2.GPT2Config.tiny()
    params = gpt2.init_params(cfg, jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=gpt2.make_loss_fn(cfg),
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
            "bf16": {"enabled": False},
        })
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (engine.train_batch_size, 32))
    batch = llama.causal_lm_batch(ids)
    losses = [float(engine.train_batch(batch).loss) for _ in range(8)]
    assert losses[-1] < losses[0], losses


def test_bert_mlm_forward_and_mask():
    cfg = bert.BertConfig.tiny()
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 16))
    mask = np.ones((2, 16), np.int32)
    mask[1, 8:] = 0  # padded tail
    logits = bert.forward(cfg, params, jnp.asarray(ids), attention_mask=jnp.asarray(mask))
    assert logits.shape == (2, 16, cfg.vocab_size)
    # padded positions must not influence unpadded outputs
    ids2 = ids.copy()
    ids2[1, 12] = (ids2[1, 12] + 7) % cfg.vocab_size
    l2 = bert.forward(cfg, params, jnp.asarray(ids2), attention_mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(logits[1, :8]), np.asarray(l2[1, :8]), atol=1e-5)


@pytest.mark.slow
def test_bert_trains_zero1():
    cfg = bert.BertConfig.tiny()
    params = bert.init_params(cfg, jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=bert.make_loss_fn(cfg),
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 1},
            "bf16": {"enabled": False},
        })
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (engine.train_batch_size, 16))
    labels = np.full_like(ids, -100)
    labels[:, ::4] = ids[:, ::4]  # predict every 4th token
    losses = [float(engine.train_batch({"input_ids": ids, "labels": labels}).loss) for _ in range(6)]
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_llama_trains_zero3_bf16():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=llama.make_loss_fn(cfg),
        model_parameters=params,
        config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
            "zero_optimization": {"stage": 3, "param_persistence_threshold": 0},
        })
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (engine.train_batch_size, 32))
    batch = llama.causal_lm_batch(ids)
    losses = [float(engine.train_batch(batch).loss) for _ in range(8)]
    assert losses[-1] < losses[0], losses


def test_cross_entropy_ignore_index():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.array([[1, -100, 2, -100]])
    loss = cross_entropy_loss(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(8), rtol=1e-5)
