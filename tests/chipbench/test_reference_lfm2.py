"""The plain LFM2 reference against its written sources, and the cell's files.

The two operators and the block against ``transformers.models.lfm2`` (torch on
the CPU, the same weights, a small size): ``Lfm2ShortConv.slow_forward``,
``Lfm2Attention``, ``Lfm2DecoderLayer``.  The sparse block, which the installed
``transformers`` does not have, against its equations written out token by
token.  Then the new readers on hand-built traces, the readers the cell
borrows, and the configuration's arithmetic."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common
from chipbench.readers import (chunk_ms_per_ktok, conv_mixer_share, conv_state_bytes_per_seq,
                               conv_state_move_share, kv_write_share, moe_expert_ffn_roofline,
                               moe_ffn_share, moe_row_fill, paged_attention_roofline, table_fill)
from chipbench.reduce import lfm2_shapes, shapes, xplane
from chipbench.references import lfm2 as ref
from tests.chipbench.conftest import ROOT

TYPES = ["conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv"]
SIZES = {"hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
         "num_hidden_layers": 9, "num_dense_layers": 1, "layer_types": TYPES,
         "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 8,
         "num_experts_per_tok": 4, "norm_topk_prob": True, "use_expert_bias": True,
         "routed_scaling_factor": 1, "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
         "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "vocab_size": 256,
         "max_position_embeddings": 512}
S = 13  # tokens of the one sequence


@pytest.fixture(scope="module")
def params():
    drawn = ref.init_params(SIZES, jax.random.PRNGKey(3), jnp.float32)
    # gains that are not one, or a gain laid out wrongly would change nothing
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 64))

    def off_one(path, leaf):
        names = [getattr(p, "key", None) for p in path]
        if any(n in ("op_norm", "ffn_norm", "q_norm", "k_norm", "final_norm") for n in names):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(off_one, drawn)


def layer(params, segment, position, repeat=0):
    return jax.tree_util.tree_map(lambda a: np.asarray(a[repeat]), params["segments"][segment][position])


def hf_layer(kind, w):
    """A ``transformers`` ``Lfm2DecoderLayer`` of that kind holding the weights ``w``."""
    torch = pytest.importorskip("torch")
    lfm2_hf = pytest.importorskip("transformers.models.lfm2.modeling_lfm2")
    from transformers.models.lfm2.configuration_lfm2 import Lfm2Config
    cfg = Lfm2Config(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=1,
                     num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
                     layer_types=[kind], block_auto_adjust_ff_dim=False, norm_eps=1e-5,
                     rope_theta=1e6, conv_bias=False)
    cfg._attn_implementation = "eager"
    block = lfm2_hf.Lfm2DecoderLayer(cfg, 0).eval()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    state = {"operator_norm.weight": t(w["op_norm"]), "ffn_norm.weight": t(w["ffn_norm"]),
             "feed_forward.w1.weight": t(w["mlp"]["w_gate"].T),
             "feed_forward.w3.weight": t(w["mlp"]["w_up"].T),
             "feed_forward.w2.weight": t(w["mlp"]["w_down"].T)}
    if kind == "conv":
        state.update({"conv.in_proj.weight": t(w["mixer"]["w_in"].T),
                      "conv.out_proj.weight": t(w["mixer"]["w_out"].T),
                      # torch's filter is [channel, 1, tap]; tap j weighs z_{t-2+j}
                      "conv.conv.weight": t(w["mixer"]["filter"].T[:, None, :])})
    else:
        a = w["attn"]
        state.update({"self_attn.q_proj.weight": t(a["wq"].T), "self_attn.k_proj.weight": t(a["wk"].T),
                      "self_attn.v_proj.weight": t(a["wv"].T), "self_attn.out_proj.weight": t(a["wo"].T),
                      "self_attn.q_layernorm.weight": t(a["q_norm"]),
                      "self_attn.k_layernorm.weight": t(a["k_norm"])})
    block.load_state_dict(state)
    rotary = lfm2_hf.Lfm2RotaryEmbedding(cfg)
    return torch, block, rotary


def test_the_conv_operator_is_transformers_slow_forward(params):
    w = layer(params, 0, 0)  # the leading dense layer: conv + FFN
    torch, block, _ = hf_layer("conv", w)
    u = np.random.default_rng(0).normal(size=(S, 64)).astype(np.float32)
    with torch.no_grad():
        want = block.conv.slow_forward(torch.tensor(u)[None])[0].numpy()
    np.testing.assert_allclose(np.asarray(ref.short_conv(jnp.asarray(u), w["mixer"])), want,
                               atol=2e-5, rtol=0)


def test_the_attention_operator_is_transformers_lfm2attention(params):
    w = {**layer(params, 1, 0), "mlp": layer(params, 0, 0)["mlp"]}  # an attention layer's operator
    torch, block, rotary = hf_layer("full_attention", w)
    u = np.random.default_rng(1).normal(size=(S, 64)).astype(np.float32)
    positions = torch.arange(S)[None]
    mask = torch.full((S, S), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want, _ = block.self_attn(torch.tensor(u)[None], rotary(torch.tensor(u)[None], positions),
                                  mask)
    got = ref.attention(SIZES, jnp.asarray(u), w["attn"], jnp.arange(S))
    np.testing.assert_allclose(np.asarray(got), want[0].numpy(), atol=2e-5, rtol=0)


@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_the_block_is_transformers_decoder_layer(params, kind):
    dense = layer(params, 0, 0)
    w = dense if kind == "conv" else {**layer(params, 1, 0), "mlp": dense["mlp"]}
    w = {k: v for k, v in w.items() if k != "moe"}
    torch, block, rotary = hf_layer(kind, w)
    x = np.random.default_rng(2).normal(size=(S, 64)).astype(np.float32)
    positions = torch.arange(S)[None]
    mask = torch.full((S, S), float("-inf")).triu(1)[None, None]
    with torch.no_grad():
        want = block(torch.tensor(x)[None], rotary(torch.tensor(x)[None], positions),
                     attention_mask=mask)[0].numpy()
    got = ref.block(SIZES, jnp.asarray(x), w, jnp.arange(S), None, None)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5, rtol=0)


def test_the_sparse_block_is_its_equations_token_by_token(params):
    w = layer(params, 1, 1)  # a conv layer of the first period, with experts
    experts = {k: np.asarray(v[1]) for k, v in params["experts"].items()}  # its experts: layer 1
    n = np.random.default_rng(5).normal(size=(S, 64)).astype(np.float32)
    wg, bias = w["moe"]["gate"]["wg"], w["moe"]["gate"]["bias"]
    want, changed = np.zeros_like(n), 0
    for t in range(S):
        s = 1 / (1 + np.exp(-(n[t] @ wg)))
        picks = np.argsort(-(s + bias), kind="stable")[:4]
        changed += sorted(picks) != sorted(np.argsort(-s, kind="stable")[:4])
        weights = s[picks] / (s[picks].sum() + 1e-6) * 1
        for e, weight in zip(picks, weights):
            gate, up = n[t] @ experts["w_gate"][e], n[t] @ experts["w_up"][e]
            want[t] += weight * ((gate / (1 + np.exp(-gate)) * up) @ experts["w_down"][e])
    assert changed >= S // 3  # the drawn bias does change picks: it is tested
    combine = ref.router(SIZES, jnp.asarray(n), w["moe"]["gate"])
    got = ref.experts_ffn(jnp.asarray(n), combine, params["experts"], 1)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)
    assert np.allclose(np.asarray(combine).sum(axis=1), 1.0, atol=1e-5)  # renormalised


def test_a_later_token_changes_no_earlier_logit_and_every_layer_is_run(params):
    ids = np.random.default_rng(6).integers(0, 256, 16)
    a = np.asarray(ref.logits_rows(SIZES, params, ids, [3, 9]))
    b = np.asarray(ref.logits_rows(SIZES, params, np.concatenate([ids[:10], ids[:6]]), [3, 9]))
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)
    assert ref.segments(SIZES) == [(0, 1, 1), (1, 4, 2)]
    published = common.load_json("published", "lfm2-24b-a2b.json")["config"]
    assert ref.segments(published) == [(0, 1, 2), (2, 4, 9), (38, 1, 1), (39, 1, 1)]


# ------------------------------------------------------------------ the cell's files
def test_the_configuration_is_the_published_row_cut_in_depth_alone():
    spec = common.load_json("configs", "lfm2-24b-a2b-serve-10l.json")
    published = common.load_json("published", "lfm2-24b-a2b.json")["config"]
    assert sorted(spec["reduced"]) == ["num_hidden_layers"] and spec["num_hidden_layers"] == 10
    assert all(spec[k] == v for k, v in published.items() if k != "num_hidden_layers")
    assert (spec["num_experts"], spec["num_experts_per_tok"], spec["vocab_size"]) == (64, 4, 65536)
    sizes = common.published_sizes(spec, False)
    kinds = ref.layer_kinds(sizes)
    assert kinds[:2] == [("conv", True)] * 2  # both leading dense layers, then two whole periods
    assert [k for k, _ in kinds[2:]] == ["full_attention", "conv", "conv", "conv"] * 2
    shapes = jax.eval_shape(lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.bfloat16))
    assert common.count_params(shapes) == 5_267_090_176  # the file's arithmetic: 10.53 GB in bf16
    assert shapes["experts"]["w_gate"].shape == (8, 64, 2048, 1536)
    module, cfg = common.program_model(spec, sizes)
    assert jax.tree_util.tree_structure(jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0)))) == jax.tree_util.tree_structure(shapes)
    cache = jax.eval_shape(lambda: module.init_paged_cache(cfg, 1024, 128))
    assert cache["k"].shape == (2, 1024, 4, 128, 128)  # two attention layers, two heads of 64 a row
    assert cache["state"].shape == (8, 33, 2, 2048) and module.state_bytes_per_seq(cfg) == 65536
    for name in ("tie_word_embeddings", "head_dim", "dense_width", "sparse_block", "weights"):
        assert name in spec["assumed"]
    rehearsal = common.published_sizes(spec, True)
    assert {k for k, _ in ref.layer_kinds(rehearsal)} == {"conv", "full_attention"}
    assert ref.segments(rehearsal) == [(0, 1, 1), (1, 4, 2)] and rehearsal["num_experts"] == 8


US = 1_000_000  # ns in the unit of the durations below (a millisecond)
LEAF = (8, 33, 2, 2048)
CHUNK = [("%fusion.1 = bf16[1,256,6144]{2,1,0} fusion(...)", 90),      # u W_in
         ("%fusion.2 = bf16[32,2,2048]{2,1,0} fusion(...)", 4),         # the rows' slots read
         ("%fusion.3 = bf16[264,2,2048]{2,1,0} fusion(...)", 6),        # the slots written: in place
         ("%fusion.4 = bf16[1,256,2048]{2,1,0} fusion(...)", 40),       # W_out or any per-token op
         ("%kv_write.3 = (bf16[2048,4,128,128]{3,2,1,0}, bf16[2048,4,128,128]{3,2,1,0}) custom-call(...)", 9),
         ("%paged_attention.4 = bf16[32,4,2048,128]{3,2,1,0} custom-call(...)", 200),
         ("%gmm.5 = bf16[1024,1536]{1,0} custom-call(...)", 300)]
DECODE = [("%fusion.7 = bf16[32,1,6144]{2,1,0} fusion(...)", 30),
          ("%fusion.8 = bf16[32,3,2048]{2,1,0} fusion(...)", 3),        # the rows beside the new value
          ("%fusion.9 = bf16[32,1,2048]{2,1,0} fusion(...)", 20),       # any per-token op of a decode step
          ("%copy.1 = bf16[8,33,2,2048]{3,2,1,0} copy(...)", 50)]       # a planted whole-state copy


def trace_of(*programs):
    ops, modules = [], []
    for i, (body, name) in enumerate(programs):
        t = 10_000 * US * i
        modules.append((f"jit_{name}(1)", t, 6000 * US))
        for op, us in body:
            ops.append((xplane.short_name(op), t, us * US))
            t += us * US
    return xplane.Reduction({"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": []})


def serve_run(**fields):
    spec = common.load_json("configs", "lfm2-24b-a2b-serve-10l.json")
    fields = {"kind": "serve", "trace": None, "sizes": common.published_sizes(spec, False),
              "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
              "lengths": [256, 2048], "max_new_tokens": 128, "prompt_tokens": 2304,
              "counters": {"moe_routed_rows": 4096, "moe_expert_rows": 8192, "table_slots": 640,
                           "live_blocks": 200},
              "pool_shapes": [(2, 1024, 4, 128, 128), LEAF], **fields}
    return types.SimpleNamespace(**fields)


def test_the_conv_readers_count_what_is_certain_and_tell_a_whole_state_copy():
    assert lfm2_shapes.state_leaf([(2, 1024, 4, 128, 128), LEAF]) == LEAF
    assert lfm2_shapes.state_bytes_per_seq([LEAF]) == 65536 == conv_state_bytes_per_seq.read(serve_run())[0]
    sound = serve_run(trace=trace_of((CHUNK, "fwd_n32_t256_b20"), (DECODE[:3], "burst_n32_k16")))
    value, note = conv_mixer_share.read(sound)
    assert note["in_proj_s"] == pytest.approx(120e-3) and note["state_s"] == pytest.approx(13e-3)
    assert value == pytest.approx(100 * 133e-3 / sound.trace.busy_s)
    value, note = conv_state_move_share.read(sound)
    assert (note["whole_state_operations"], note["row_operations"]) == (1, 2)
    assert note["whole_state_s"] == pytest.approx(6e-3) and note["moved_s"] == pytest.approx(13e-3)
    copied = serve_run(trace=trace_of((CHUNK, "fwd_n32_t256_b20"), (DECODE, "burst_n32_k16")))
    assert conv_state_move_share.read(copied)[1]["whole_state_s"] == pytest.approx(56e-3)
    # a program without the state (every older configuration, the parent): nothing, and no raise
    older = serve_run(pool_shapes=[(16, 368, 8, 128, 128)], trace=sound.trace)
    for reader in (conv_mixer_share, conv_state_move_share, conv_state_bytes_per_seq):
        assert reader.read(older) is None and reader.read(serve_run()) in (None, (65536.0, {
            "state_leaf": list(LEAF), "slots": 32}))
    mistral = serve_run(sizes={"hidden_size": 4096}, pool_shapes=[LEAF])
    assert conv_state_bytes_per_seq.read(mistral) is None


@pytest.mark.reads_benchmark
def test_the_borrowed_readers_are_right_for_this_cell_and_the_others_are_not():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n32_t256_b20"), (DECODE[:3], "burst_n32_k16")))
    assert kv_write_share.read(run)[1]["calls"] == 1
    assert chunk_ms_per_ktok.read(run)[1]["chunk_programs_run"] == 1
    assert moe_row_fill.read(run)[0] == 50.0 and table_fill.read(run)[0] == 31.25
    # left off the cell: the attention counts take every one of the 10 layers for an attention
    # layer (two are), so the share would pass 100%; the expert readers take the dense FFN's
    # width (11776) for an expert's (1536) and the layer count for the experts' stack (8 deep)
    assert paged_attention_roofline.read(run) is not None  # it would read, and read wrong:
    assert shapes.kv_bytes_per_token(run.sizes) == 5 * 4096  # five times the 4,096 B a token held
    assert run.sizes["intermediate_size"] == 11776 != run.sizes["moe_intermediate_size"]
    kinds = {kind for _, _, _, kind in moe_ffn_share.operations(run)}
    assert kinds == {"grouped_matmul"}  # nothing around the kernel is found under these keys
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    every = [w["name"] for w in bench["workloads"]]  # an entry without a list is read in every cell
    lists = {m["name"]: m.get("workloads", every) for m in bench["per_layer"]}
    cell = "serve.conv-chat-burst"
    for name in ("paged_attention_roofline", "pool.moved_share", "moe.ffn_share",
                 "moe.expert_ffn_roofline"):
        assert cell not in lists[name]
    for name in ("kv.write_share", "moe.row_fill", "paged.table_fill", "step.chunk_ms_per_ktok",
                 "step.burst_ms_per_step", "sched.slot_fill", "conv.mixer_share",
                 "conv.state_move_share", "conv.state_bytes_per_seq"):
        assert cell in lists[name]
    assert moe_expert_ffn_roofline.read(serve_run()) is None
