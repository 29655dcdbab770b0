"""The readers of the latent-attention cell's metrics and the counts behind
them, on hand-built inputs: ``mla_shapes`` by hand, which operations of a
traced program are the held experts' FFN, and that a program without the
kernels or the keys (the parent commit, another configuration) gives nothing
and does not raise."""

import types

import pytest

from chipbench.readers import (mla_attention_roofline, mla_kernel_share, mla_pool_bytes_per_token,
                               moe_held_ffn_share)
from chipbench.reduce import mla_shapes, xplane

US = 1_000_000  # ns in the unit of the durations below (a millisecond)
SIZES = {"hidden_size": 5120, "intermediate_size": 12288, "moe_intermediate_size": 1536,
         "num_hidden_layers": 5, "first_k_dense_replace": 1, "num_attention_heads": 128,
         "kv_lora_rank": 512, "qk_rope_head_dim": 64, "n_routed_experts": 40, "n_group": 8,
         "num_experts_per_tok": 6}
DENSE_SIZES = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 16,
               "num_attention_heads": 32, "num_key_value_heads": 8}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def serve_run(**fields):
    fields = {"kind": "serve", "trace": None, "counters": {}, "sizes": SIZES, "peaks": PEAKS,
              "lengths": [4096], "max_new_tokens": 32,
              "pool_shapes": [(5, 1024, 1, 128, 640)], **fields}
    return types.SimpleNamespace(**fields)


def traced(ops, modules):
    return xplane.Reduction({"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
                             "host": []})


def trace_of(*programs):
    ops, modules = [], []
    for i, (body, name) in enumerate(programs):
        t = 10_000 * i * US
        modules.append((f"jit_{name}(1)", t, 6000 * US))
        for op, dur_us in body:
            ops.append((op, t, dur_us * US))
            t += dur_us * US
    return traced(ops, modules)


# a chunk program: 512 slots x top-6 = 3,072 rows; a decode program: 8 x 6 = 48 -> 48 rows
CHUNK = [("%gmm.11 bf16[3072,1536]", 300), ("%gmm.12 bf16[3072,1536]", 300),
         ("%gmm.13 bf16[3072,5120]", 300),
         ("%sort.67 (s32[3072]", 5), ("%sort.66 (f32[512,160]", 3), ("%sort.65 (f32[512,8]", 1),
         ("%fusion.263 s32[160]", 4), ("%fusion.262 s32[161]", 4), ("%fusion.268 s32[183]", 4),
         ("%fusion.9 f32[512,160]", 2), ("%fusion.10 f32[512,8,20]", 2),   # softmax, the group mask
         ("%fusion.277 bf16[3072,5120]", 20), ("%multiply_multiply_fusion.2 bf16[3072,1536]", 6),
         ("%fusion.278 s32[3072]", 3), ("%compare_select_fusion.66 s32[3072,1]", 1),
         # not the held experts': the shared expert, dense layers, attention, the head
         ("%fusion.300 bf16[512,3072]", 40), ("%fusion.270 bf16[512,5120]", 50),
         ("%paged_attention.11 bf16[8,1,65536,512]", 2000), ("%fusion.150 bf16[512,25600]", 40)]
DECODE = [("%gmm.13 bf16[48,1536]", 100), ("%gmm.14 bf16[48,1536]", 100),
          ("%gmm.15 bf16[48,5120]", 100), ("%sort.77 (s32[48]", 2), ("%fusion.262 s32[161]", 3),
          ("%fusion.272 bf16[48,5120]", 4),
          ("%paged_attention.13 bf16[8,1,128,512]", 500), ("%fusion.1 bf16[8,1,5120]", 10)]
DENSE = [("%fusion.270 bf16[256,4096]", 50), ("%paged_attention.2 bf16[32,8,16,128]", 100)]


def test_mla_shapes_counts_are_the_mathematics_by_hand():
    assert mla_shapes.latent_values(SIZES) == 576 and mla_shapes.latent_bytes_per_token(SIZES) == 1152
    assert mla_shapes.pair_flops(SIZES) == 2 * 128 * (576 + 512) == 278_528  # 242 operations a byte
    assert mla_shapes.causal_pairs(0, 4) == 1 + 2 + 3 + 4
    assert mla_shapes.causal_pairs(10, 3) == 11 + 12 + 13
    assert mla_shapes.decode_pairs(100, 32) == sum(range(101, 132)) and mla_shapes.decode_pairs(100, 1) == 0
    least = mla_shapes.attention_least_seconds(SIZES, [4096, 8192], 32, PEAKS)
    pairs = 4096 * 4097 // 2 + 8192 * 8193 // 2
    assert least["prefill_compute_s"] == pytest.approx(5 * pairs * 278_528 / 197e12)
    assert least["prefill_memory_s"] == 0.0          # long prompts: compute bound by far
    decode = sum(range(4097, 4097 + 31)) + sum(range(8193, 8193 + 31))
    by_flops, by_bytes = 5 * decode * 278_528 / 197e12, 5 * (decode * 1152 + 2 * 31 * 128 * 1088 * 2) / 819e9
    assert least["decode_compute_s"] + least["decode_memory_s"] == pytest.approx(
        max(5 * sum(range(4097, 4128)) * 278_528 / 197e12,
            5 * (sum(range(4097, 4128)) * 1152 + 31 * 128 * 1088 * 2) / 819e9)
        + max(5 * sum(range(8193, 8224)) * 278_528 / 197e12,
              5 * (sum(range(8193, 8224)) * 1152 + 31 * 128 * 1088 * 2) / 819e9))
    assert 0.9 < by_flops / by_bytes < 1.1           # the ridge: neither bound is slack
    assert least["seconds"] == pytest.approx(sum(v for k, v in least.items() if k != "seconds"))
    # a short prompt's prefill is bound by moving q and the output
    short = mla_shapes.attention_least_seconds(SIZES, [64], 1, PEAKS)
    assert short["prefill_memory_s"] > 0 and short["prefill_compute_s"] == 0.0


def test_roofline_and_kernel_share_read_the_kernels_events():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n8_t512_b68"), (DECODE, "burst_n8_k16")))
    value, note = mla_attention_roofline.read(run)
    least = mla_shapes.attention_least_seconds(SIZES, [4096], 32, PEAKS)["seconds"]
    assert note["kernel_s"] == pytest.approx(2.5) and value == pytest.approx(100 * least / 2.5)
    assert 0 < value < 100 and note["mostly"] == "prefill_compute_s"
    share, note = mla_kernel_share.read(run)
    assert share == pytest.approx(100 * 2.5 / run.trace.busy_s) and note["busy_s"] == pytest.approx(
        run.trace.busy_s, abs=1e-4)
    # at the roofline exactly it reads 100 and cannot pass it
    at = trace_of(([("%paged_attention.1 bf16[8,1,65536,512]", least * 1e9 / US)], "fwd_n8_t512_b68"))
    assert mla_attention_roofline.read(serve_run(trace=at))[0] == pytest.approx(100.0, rel=1e-6)


def test_pool_bytes_per_token_reads_the_pools_leaves():
    assert mla_pool_bytes_per_token.read(serve_run())[0] == 1280.0
    assert mla_pool_bytes_per_token.read(serve_run(pool_shapes=[(5, 1024, 1, 128, 576)]))[0] == 1152.0
    # the day someone expands the cache to heads: K [128 x 192] and V [128 x 128] a token
    expanded = [(5, 1024, 128, 128, 128), (5, 1024, 128, 128, 192)]
    assert mla_pool_bytes_per_token.read(serve_run(pool_shapes=expanded))[0] == 81920.0


def test_the_held_experts_operations_are_found_by_kind_under_this_configurations_keys():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n8_t512_b68"), (DECODE, "burst_n8_k16"),
                                   (DENSE, "pick_n8")))
    kinds = {}
    for prog, name, ns, kind in moe_held_ffn_share.operations(run):
        kinds.setdefault(kind, set()).add(name.split(" ")[0])
    assert kinds["grouped_matmul"] == {f"%gmm.{i}" for i in (11, 12, 13, 14, 15)}
    assert kinds["sort"] == {"%sort.67", "%sort.66", "%sort.65", "%sort.77"}
    assert kinds["group_metadata"] == {"%fusion.263", "%fusion.262", "%fusion.268"}  # 4 x 40 groups
    assert kinds["router"] == {"%fusion.9", "%fusion.10"}          # [512, 160] and [512, 8, 20]
    assert kinds["dispatch"] == {"%fusion.277", "%multiply_multiply_fusion.2", "%fusion.278",
                                 "%compare_select_fusion.66", "%fusion.272"}
    value, note = moe_held_ffn_share.read(run)
    ffn_us = 900 + 9 + 12 + 4 + 30 + 300 + 2 + 3 + 4
    assert note["ffn_s"] == pytest.approx(ffn_us / 1e3, abs=1e-4)
    assert value == pytest.approx(100 * ffn_us * US / 1e9 / run.trace.busy_s, rel=1e-3)


@pytest.mark.parametrize("reader", [mla_attention_roofline, mla_kernel_share,
                                    mla_pool_bytes_per_token, moe_held_ffn_share])
def test_a_program_without_the_kernels_or_the_keys_gives_nothing(reader):
    dense_trace = trace_of((DENSE, "fwd_n32_t256_b12"))
    no_kernel = trace_of(([("%fusion.270 bf16[512,5120]", 50)], "fwd_n8_t512_b68"))
    runs = [serve_run(sizes=DENSE_SIZES, trace=dense_trace, pool_shapes=[(16, 368, 8, 128, 128)]),
            serve_run(sizes=DENSE_SIZES), serve_run(kind="train", trace=dense_trace)]
    if reader is not mla_pool_bytes_per_token:
        runs += [serve_run(), serve_run(trace=no_kernel)]  # no trace; a trace without the kernels
    else:
        runs += [serve_run(pool_shapes=[]), types.SimpleNamespace(kind="serve", sizes=SIZES)]
    for run in runs:
        assert reader.read(run) is None
