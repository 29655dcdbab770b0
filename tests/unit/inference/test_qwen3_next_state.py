"""Qwen3-Next (ISSUE 43): Gated DeltaNet layers whose memory of a sequence is a
float32 matrix a head and a short shift, two leaves of a state tree in a slot
beside the paged KV pool; gated attention over heads two lane tiles wide in the
fourth layer; a gated shared expert beside the routed ones.

The program (``models/qwen3_next.py`` on ``transformer.paged_forward``, through
the engine's scheduler, manager, bursts) against the plain reference
(``chipbench/references/qwen3_next.py``: whole sequences, the delta rule token
by token, no state, no cache) in float32 at a size with two whole periods.
The shared cases are ``family_contract.py``'s; this file builds two engine
configurations (``served``, ``oracle``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.references import qwen3_next as ref
from deepspeed_tpu.models import qwen3_next
from deepspeed_tpu.models.transformer import STATE
from deepspeed_tpu.moe.serving import sparse_moe_ffn
from deepspeed_tpu.ops.linear_attention import CHUNK
from tests.unit.inference.family_contract import Family, Pool, StatefulContract

HELD = 4  # of 16 experts: one chip's share of four
SIZES = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 16, "hidden_act": "silu",
         "hidden_size": 64, "intermediate_size": 128, "linear_conv_kernel_dim": 4,
         "linear_key_head_dim": 8, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
         "linear_value_head_dim": 8, "max_position_embeddings": 512, "mlp_only_layers": [],
         "model_type": "qwen3_next", "moe_intermediate_size": 32, "norm_topk_prob": True,
         "num_attention_heads": 4, "num_experts": HELD, "num_experts_per_tok": 4,
         "num_hidden_layers": 8, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
         "rms_norm_eps": 1e-6, "rope_scaling": None, "rope_theta": 10000000,
         "shared_expert_intermediate_size": 32, "tie_word_embeddings": False,
         "use_sliding_window": False, "vocab_size": 256}
CFG = qwen3_next.Qwen3NextConfig.tiny(experts=ref.EP_CHIPS * HELD, local_experts=HELD)
NB, BS, SLOTS = 72, 4, 4
TOL = 2e-5  # of the expert layer alone
NORMS = {"op_norm", "ffn_norm", "q_norm", "k_norm", "final_norm", "norm"}


def off_neutral(names, leaf, noise):  # a gain of the wrong kind or place must show
    return leaf + 0.3 * noise(leaf.shape) if NORMS & set(names) else leaf


def layout(h, own, cache):
    assert qwen3_next.layer_segments(qwen3_next.Qwen3NextConfig()) == [(0, 4, 12)]
    assert own["experts"]["w_gate"].shape[:2] == (8, HELD)  # the held experts of every layer
    assert own["segments"][0][0]["moe"]["gate"]["wg"].shape[-1] == 4 * HELD  # the router's width
    # attention layers alone in the pool; the DeltaNet layers' two leaves apart, the matrix float32
    assert cache["k"].shape == cache["v"].shape == (2, NB, 2, BS, 16)
    assert cache[STATE]["conv"].shape == (6, SLOTS + 1, 3, 2 * 16 + 32)
    assert cache[STATE]["recurrent"].shape == (6, SLOTS + 1, 4, 8, 8)
    half = h.fresh_cache(jnp.bfloat16)[STATE]
    assert (half["conv"].dtype, half["recurrent"].dtype) == (jnp.bfloat16, jnp.float32)
    full = qwen3_next.Qwen3NextConfig(num_layers=12)  # the benchmark's cut: three periods
    assert qwen3_next.state_bytes_per_seq(full) == 9 * (2097152 + 49152) == 19316736
    with pytest.raises(NotImplementedError, match="mlp_only_layers"):
        qwen3_next.Qwen3NextConfig(mlp_only_layers=[0])


def wave(h, seen):
    c, eng = seen.counters, seen.engine
    by_leaf = eng.health()["state"]["state_bytes_by_leaf"]
    assert by_leaf == {"conv": 6 * 3 * 64 * 4, "recurrent": 6 * 4 * 8 * 8 * 4}
    assert eng.state_snapshot()["state"]["state_bytes_by_leaf"] == by_leaf
    # the scan's counters: a pass that walks chunks counts its live tokens (a mixed pass's decode
    # rows among them) in each of the six DeltaNet layers; a decode step or a burst walks none
    assert c["scan_positions"] == c["scan_chunks"] * CHUNK
    assert 0 < c["scan_live_positions"] <= c["scan_positions"]
    assert c["scan_live_positions"] % 6 == 0
    assert sum(map(len, seen.prompts)) <= c["scan_live_positions"] // 6 < c["live_tokens"]
    assert c["moe_routed_rows"] == c["live_tokens"] * 4 * 8


FAMILY = Family(
    module=qwen3_next, reference=ref, sizes=SIZES, config=CFG,
    tolerance=3e-4,
    tolerance_reason="""3e-4 of the largest logit.  LFM2's 2e-5 is 1.25e-4 of its own (a tied
    0.02-scale head gives logits of 0.16; this head is untied at 1/sqrt(D) and
    its logits reach 3-4), and this model at this draw turns a relative noise of
    1e-7 in the weights into 1.1e-5 of the logits (measured on the reference
    alone), so two float32 programs of eight such layers (the chunked scan
    against the token-by-token rule, sorted dispatch against every expert) read
    1e-5 to 1.4e-4 apart, as HF's own float32 and float64 runs do
    (``tests/chipbench/test_reference_qwen3_next.py``).  A state that is not
    carried, a gate left out or a gain of the wrong kind reads 1e-2 and more.""",
    off_neutral=off_neutral, pool=Pool(NB, BS, 48, SLOTS), state_leaves=("conv", "recurrent"),
    segments=[(0, 4, 2)],
    # a chunk continues from the matrix and the shift its sequence's slot holds, across the scan's
    # own chunks of 64 and the step's;
    # the mixed step: each row is laid onto a chunk's edge and scanned from its own slot's matrix;
    # the wave: one decode-only, one cut in three, one in five
    layout=layout, wave=wave)


class TestQwen3Next(StatefulContract):
    family = FAMILY

    def test_the_expert_layer_is_this_chips_share_and_the_shared_expert_is_gated(self, h):
        """``sparse_moe_ffn`` against the reference's ``layer_parts`` for chip 0:
        a router over 16, 4 experts held, picks elsewhere add nothing; the shared
        expert times one sigmoid gate a token."""
        params = h.params
        moe = jax.tree_util.tree_map(lambda a: a[0], params["segments"][0][1]["moe"])
        x = jax.random.normal(jax.random.PRNGKey(9), (37, 64))
        with jax.default_matmul_precision("highest"):
            got = sparse_moe_ffn({**moe, "experts": params["experts"]}, x, 4, True, layer=jnp.int32(1))
            routed, shared = ref.layer_parts(SIZES, {**moe, "experts": params["experts"]}, x, layer=1)
            ungated = ref.swiglu(x, moe["shared"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(routed + shared), atol=TOL, rtol=0)
        assert np.abs(np.asarray(shared - ungated)).max() > 0.05  # the gate is not one
