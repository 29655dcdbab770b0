"""The plain OLMoE reference against the program at a tiny size on the CPU:
``forward_paged`` driven as the engine drives it (prefill in chunks, then
decode, through the paged cache; compacted and padded), four wrong readings
of the architecture that each have to fail, and the cell's rehearsal."""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common
from chipbench.references import olmoe as ref

SIZES = {"hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256,
         "max_position_embeddings": 128, "rms_norm_eps": 1e-5, "rope_theta": 10000,
         "num_experts": 8, "num_experts_per_tok": 4, "norm_topk_prob": False}
BLOCK, CHUNK, BOUND = 8, 8, 16
LENGTHS, DECODED = (29, 11, 17), 3  # prompt tokens, then tokens fed one at a time
# Both sides are float32 at ``highest`` matmul precision on the CPU, so they
# differ by the order of their sums alone (a grouped matmul over sorted rows
# against sixty-four dense ones, attention over gathered blocks against
# blocks of queries): 8e-7 of the largest logit was read, compacted and padded.
# A wrong reading of the architecture moves the logits by 20% (renormalised
# weights) to 92% (no QK-norm) of it; each has to pass 100 tolerances.
TOLERANCE = 2e-5


def drawn(seed):
    params = jax.jit(lambda k: ref.init_params(SIZES, k, jnp.float32))(jax.random.PRNGKey(seed))
    # gains that are not one, or a gain laid out wrongly would change nothing
    for i, name in enumerate(("q_norm", "k_norm")):
        gain = params["layers"]["attn"][name]
        params["layers"]["attn"][name] = gain + 0.5 * jax.random.normal(
            jax.random.PRNGKey(100 + i), gain.shape)
    rng = np.random.default_rng(seed)
    return params, [rng.integers(0, 256, n + DECODED).tolist() for n in LENGTHS]


def program_config(**changes):
    from deepspeed_tpu.models import olmoe
    return dataclasses.replace(olmoe.OlmoeConfig(
        vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=2, num_heads=4,
        num_kv_heads=4, num_experts=8, top_k=4, max_seq_len=128, rope_theta=10000.0,
        rms_eps=1e-5), **changes)


def program_logits(cfg, params, seqs, bound):
    """Every position's logits from ``forward_paged``: steps of at most
    ``BOUND`` live tokens over rows ``[4, CHUNK]`` (a row is dead throughout)
    while prompts last, then ``[4, 1]`` steps, all through one paged pool."""
    from deepspeed_tpu.models import olmoe
    rows, width = 4, 8
    kv = olmoe.init_paged_cache(cfg, rows * width + 1, BLOCK, dtype=jnp.float32)
    tables = np.full((rows, width), rows * width, np.int32)  # unused entries: the trash block
    for r in range(len(seqs)):
        tables[r] = np.arange(r * width, (r + 1) * width)
    seen = [0] * len(seqs)
    out = [np.zeros((len(s), cfg.vocab_size), np.float32) for s in seqs]
    fwd = jax.jit(lambda kv, tok, n, start: olmoe.forward_paged(
        cfg, params, tok, n, start, jnp.asarray(tables), kv, block_size=BLOCK,
        live_token_bound=bound))
    compacted = 0
    while any(seen[r] < len(s) for r, s in enumerate(seqs)):
        prefill = any(seen[r] < len(s) - DECODED for r, s in enumerate(seqs))
        t, budget = (CHUNK, BOUND) if prefill else (1, rows)
        tok, n = np.zeros((rows, t), np.int32), np.zeros(rows, np.int32)
        for r, s in enumerate(seqs):
            end = len(s) - DECODED if prefill else len(s)
            n[r] = max(0, min(end - seen[r], t, budget))
            budget -= n[r]
            tok[r, :n[r]] = s[seen[r]:seen[r] + n[r]]
        start = np.asarray(seen + [0] * (rows - len(seqs)), np.int32)
        with jax.default_matmul_precision("highest"):
            logits, kv = fwd(kv, jnp.asarray(tok), jnp.asarray(n), jnp.asarray(start))
        compacted += bound is not None and rows * t > bound
        for r in range(len(seqs)):
            out[r][seen[r]:seen[r] + n[r]] = np.asarray(logits[r, :n[r]])
            seen[r] += int(n[r])
    assert compacted == (0 if bound is None else 4)  # the chunk steps, and they alone
    return out


def worst_error(cfg, params, seqs, bound):
    got = program_logits(cfg, params, seqs, bound)
    worst = 0.0
    for ids, mine in zip(seqs, got):
        want = np.asarray(ref.logits_rows(SIZES, params, ids, list(range(len(ids)))))
        worst = max(worst, float(np.abs(mine - want).max() / np.abs(want).max()))
    return worst


def test_reference_imports_nothing_of_the_programs_models_and_sets_highest_precision():
    source = inspect.getsource(ref)
    assert "deepspeed_tpu" not in source.split('"""', 2)[2]
    assert 'jax.default_matmul_precision("highest")' in source


@pytest.mark.parametrize("bound", [BOUND, None], ids=["compacted", "padded"])
def test_paged_forward_in_chunks_then_decode_equals_the_reference(bound):
    params, seqs = drawn(3)
    assert worst_error(program_config(), params, seqs, bound) < TOLERANCE


def per_head_qk_norm(config, tp_axis):
    def norm(x, gain):
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + config.rms_eps)
        return (x32 * gain.reshape(x.shape[-2:])).astype(x.dtype)
    return lambda lp, q, k: (norm(q, lp["attn"]["q_norm"]), norm(k, lp["attn"]["k_norm"]))


def softmax_over_the_picked_only(wg, x, top_k, renormalise):
    logits = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(logits, top_k)
    return jax.nn.softmax(top, axis=-1), idx.astype(jnp.int32)


@pytest.mark.parametrize("wrong", ["top_k_weights_renormalised", "qk_norm_left_out",
                                   "qk_norm_per_head", "router_softmax_over_the_picked_only"])
def test_each_wrong_reading_of_the_architecture_fails_the_tolerance(wrong, monkeypatch):
    from deepspeed_tpu.models import mixtral
    from deepspeed_tpu.moe import serving
    cfg = program_config()
    if wrong == "top_k_weights_renormalised":
        cfg = program_config(norm_topk_prob=True)
    elif wrong == "qk_norm_left_out":
        cfg = program_config(qk_norm=False)
    elif wrong == "qk_norm_per_head":
        monkeypatch.setattr(mixtral, "whole_width_qk_norm", per_head_qk_norm)
    else:
        monkeypatch.setattr(serving, "route", softmax_over_the_picked_only)
    params, seqs = drawn(3)
    assert worst_error(cfg, params, seqs, BOUND) > 100 * TOLERANCE


def test_padding_after_the_last_row_changes_nothing_and_query_blocks_neither():
    params, seqs = drawn(4)
    ids = seqs[1]
    a = np.asarray(ref.logits_rows(SIZES, params, ids, [5, len(ids) - 1]))
    b = np.asarray(ref.logits_rows(SIZES, params, ids + [0] * 12, [5, len(ids) - 1]))
    assert np.allclose(a, b, atol=1e-5)
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (50, 4, 16)) for i in range(3))
    assert np.allclose(ref.attention(q, k, v, q_block=8), ref.attention(q, k, v, q_block=64),
                       atol=1e-5)


def test_router_weights_are_the_softmax_over_all_experts_unrenormalised():
    m = jax.random.normal(jax.random.PRNGKey(0), (6, 64))
    wg = jax.random.normal(jax.random.PRNGKey(1), (64, 8)) / 8
    combine = np.asarray(ref.router(m, wg, 4, False))
    probs = np.asarray(jax.nn.softmax(m @ wg, axis=-1))
    assert ((combine > 0).sum(axis=1) == 4).all()
    assert np.allclose(combine[combine > 0], probs[combine > 0])
    assert (combine.sum(axis=1) < 0.999).all()  # what is left with the other experts is dropped
    assert np.allclose(np.asarray(ref.router(m, wg, 4, True)).sum(axis=1), 1.0)


def test_engine_agrees_and_the_fp8_control_does_not(rehearse):
    """The new cell's rehearsal: inside its limits as built, outside them
    with the weights rounded through fp8."""
    sound = rehearse("--workload", "serve.moe-chat-burst", "--seed", "11", "--seconds", "0")
    control = rehearse("--workload", "serve.moe-chat-burst", "--seed", "11", "--seconds", "0",
                       "--control", "1")
    spec = common.load_json("configs", "olmoe-1b-7b-serve-8l.json")
    limit = common.correct_limits(spec, rehearse=True)["logit_rel_rms_limit"]
    assert sound.line["would_be_correct"] is True
    assert control.line["would_be_correct"] is False
    assert sound.number("logit_rel_rms") < limit < control.number("logit_rel_rms")
    assert control.number("logit_rel_rms") > 3 * sound.number("logit_rel_rms")
