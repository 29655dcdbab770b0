"""The cell ``serve.dsa-long-prompt``: its configuration's arithmetic, its five
``dsa.*`` readers on hand-built traces and counters (the rooflines at their
extremes), the readers it borrows, and the readers that would read and read
wrong, which are left off it."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import common
from chipbench.generators.waves import Traffic
from chipbench.readers import (chunk_ms_per_ktok, dsa_attended_per_selected, dsa_attention_roofline,
                               dsa_index_roofline, dsa_indexer_share, dsa_selected_share,
                               gdn_scan_share, kv_write_share, mla_attention_roofline,
                               mla_pool_bytes_per_token, moe_held_ffn_share, q_fill, table_fill)
from chipbench.reduce import dsa_shapes, xplane
from chipbench.references import glm_moe_dsa as ref
from tests.chipbench.conftest import ROOT

CONFIG, CELL = "glm-5-serve-ep16-7l", "serve.dsa-long-prompt"
POOL = [(7, 1024, 1, 128, 128), (7, 1024, 1, 128, 640)]
US = 1_000_000  # ns in the unit of the durations below (a millisecond)
CHUNK = [("%dsa_index_scores.1 = f32[1088,16896]{1,0} custom-call(...)", 40),   # the score kernel
         ("%fusion.2 = f32[512,16896]{1,0} fusion(...)", 5),                     # the scores gathered back
         ("%fusion.3 = u32[512,16896]{1,0} fusion(...)", 6),                     # their ordered image
         ("%fusion.4 = u32[512,1]{1,0} fusion(...)", 60),                        # a counting pass
         ("%fusion.5 = s32[512,1]{1,0} fusion(...)", 20),                        # the tie's cutoff
         ("%fusion.6 = pred[512,16896]{1,0} fusion(...)", 4),                    # the selection
         ("%fusion.7 = f32[76,33,8,512]{3,2,1,0} fusion(...)", 3),               # laid out for the kernel
         ("%fusion.8 = bf16[1,512,12288]{2,1,0} fusion(...)", 30),               # the dense FFN: not ours
         ("%fusion.9 = f32[512,6144]{1,0} fusion(...)", 2),                      # a norm in float32: not ours
         ("%fusion.10 = f32[512,256]{1,0} fusion(...)", 1),                      # the router: not ours
         ("%kv_write.11 = (bf16[7168,1,128,128]{3,2,1,0}, bf16[7168,1,128,640]{3,2,1,0}) custom-call(...)", 3),
         ("%paged_attention.12 = bf16[1,36864,512]{2,1,0} custom-call(...)", 200),
         ("%gmm.13 = bf16[4096,2048]{1,0} custom-call(...)", 50)]


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
    spec = common.load_json("configs", CONFIG + ".json")
    fields = {"kind": "serve", "trace": None, "sizes": common.published_sizes(spec, False),
              "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
              "lengths": [8875, 15701], "max_new_tokens": 32, "prompt_tokens": 24576, "forwards": 4,
              "counters": {"dsa_causal_keys": 7 * 20_000_000, "dsa_selected_keys": 7 * 4_000_000,
                           "dsa_scored_keys": 7 * 21_000_000, "dsa_attended_keys": 7 * 22_000_000,
                           "table_slots": 1024, "live_blocks": 400, "live_tokens": 2048,
                           "attn_token_slots": 2104, "token_slots": 2048},
              "pool_shapes": POOL, **fields}
    return types.SimpleNamespace(**fields)


def test_the_configuration_is_the_published_model_cut_to_one_chips_share():
    spec = common.load_json("configs", CONFIG + ".json")
    published = common.load_json("published", spec["published"] + ".json")["config"]
    assert sorted(spec["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert all(spec[k] == v for k, v in published.items() if k not in spec["reduced"])
    assert (spec["num_hidden_layers"], spec["n_routed_experts"], spec["vocab_size"]) == (7, 16, 19360)
    assert 16 * spec["n_routed_experts"] == published["n_routed_experts"]
    assert 8 * spec["vocab_size"] == published["vocab_size"] and spec["first_k_dense_replace"] == 3
    sizes = common.published_sizes(spec, False)
    assert ref.router_width(sizes) == 256 and ref.EP_CHIPS == 16
    drawn = jax.eval_shape(lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.bfloat16))
    # the file's arithmetic (its 4,711M leaves the norms' gains and the routers' biases out)
    assert common.count_params(drawn) == 4_711_430_400
    assert drawn["layers"]["moe"]["experts"]["w_gate"].shape == (4, 16, 6144, 2048)
    assert drawn["layers"]["moe"]["gate"]["wg"].shape == (4, 6144, 256)
    assert drawn["dense_layers"]["indexer"]["wq"].shape == (3, 2048, 32 * 128)
    module, cfg = common.program_model(spec, sizes)
    assert jax.tree_util.tree_structure(jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0)))) == jax.tree_util.tree_structure(drawn)
    engine = spec["engine"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(cfg, engine["num_blocks"], engine["block_size"]))
    assert sorted({leaf.shape for leaf in jax.tree_util.tree_leaves(cache)}) == sorted(POOL)
    assert module.selected_keys(cfg) == (2048, 7)
    for name in ("attention", "indexer", "tie_rule", "hadamard_and_fp8", "num_nextn_predict_layers",
                 "router", "shared_expert", "weights"):
        assert name in spec["assumed"], name
    wave = Traffic(common.load_json("traffic", "dsa-long-prompt.json")["params"], 1, spec["vocab_size"])
    assert sum(wave.lengths) == 49152 and len(wave.lengths) == 4 <= engine["max_seqs_per_step"]
    assert engine["token_budget"] == 1024
    assert min(wave.lengths) >= 4 * spec["index_topk"]  # every compared row and decode step selects
    rehearsal = common.published_sizes(spec, True)
    scale = spec["rehearsal"]["traffic_scale"]["length_divisor"]
    assert rehearsal["index_topk"] * 4 <= 8192 // scale  # the rehearsal selects too


def test_the_dsa_readers_count_what_is_certain():
    run = serve_run()
    value, note = dsa_selected_share.read(run)
    assert value == pytest.approx(20.0) and note["causal_keys"] == 140_000_000
    value, note = dsa_attended_per_selected.read(run)
    assert value == pytest.approx(5.5) and note["scored_keys"] == 147_000_000
    traced = serve_run(trace=trace_of((CHUNK, "fwd_n8_t512_b132"), (CHUNK[-3:], "burst_n8_k16")))
    value, note = dsa_indexer_share.read(traced)
    assert (note["scores_s"], note["select_s"]) == pytest.approx((40e-3, 98e-3))
    assert value == pytest.approx(100 * 138e-3 / traced.trace.busy_s)
    sizes = run.sizes
    assert dsa_shapes.attention_pair_operations(sizes) == 139_264
    assert dsa_shapes.index_pair_operations(sizes) == 8_192
    least = dsa_shapes.attention_least_seconds(sizes, 28_000_000, 2048 * 7, run.peaks)
    assert least["compute_s"] == pytest.approx(28_000_000 * 139_264 / 197e12)
    assert least["memory_s"] == pytest.approx(2048 * 7 * 64 * 1088 * 2 / 819e9)
    value, note = dsa_attention_roofline.read(traced)
    assert value == pytest.approx(100 * least["seconds"] / 0.4) and note["mostly"] == "compute_s"
    least = dsa_shapes.index_least_seconds(sizes, 140_000_000, 512.0, run.peaks)
    assert least["compute_s"] == pytest.approx(140_000_000 * 8_192 / 197e12)
    assert least["memory_s"] == pytest.approx(140_000_000 / 512 * 256 / 819e9)
    value, note = dsa_index_roofline.read(traced)
    assert value == pytest.approx(100 * least["seconds"] / 0.04) and note["kernel_s"] == 0.04


@pytest.mark.parametrize("length", [8192, 16384])
def test_the_rooflines_read_under_100_at_their_extremes(length):
    """A kernel at the chip's peak that does exactly the counted work reads
    100; this program's kernels do more (the attention walks every causal key,
    the index kernel whole steps of blocks), so by construction each reads
    under it: here the kernels' own least times, from what they walk."""
    spec = common.load_json("configs", CONFIG + ".json")
    sizes, peaks = common.published_sizes(spec, False), {"bf16_flops_per_s": 197e12,
                                                        "hbm_bytes_per_s": 819e9}
    positions = range(length)
    causal = 7 * sum(p + 1 for p in positions)
    chosen = 7 * sum(min(p + 1, 2048) for p in positions)
    assert 100.0 * chosen / causal == pytest.approx(
        100 * (2048 * length - 2048 ** 2 / 2) / (length ** 2 / 2), rel=2e-3)  # 44% and 23%
    walked = 7 * sum(-(-(min(p // 512 * 512 + 512, length)) // 512) * 512 for p in positions)
    at_peak = walked * dsa_shapes.attention_pair_operations(sizes) / peaks["bf16_flops_per_s"]
    least = dsa_shapes.attention_least_seconds(sizes, chosen, 7 * length, peaks)
    assert 0 < least["seconds"] / at_peak < 1.0
    scored = 7 * sum(-(-(p + 1) // 512) * 512 for p in positions)
    at_peak = scored * dsa_shapes.index_pair_operations(sizes) / peaks["bf16_flops_per_s"]
    least = dsa_shapes.index_least_seconds(sizes, causal, 512.0, peaks)
    assert 0 < least["seconds"] / at_peak <= 1.0


def test_a_program_without_the_family_gives_nothing_and_does_not_raise():
    """What the parent commit, and every other configuration, hands these
    readers: no ``dsa_*`` counters, no index keys among the sizes, no such kernel."""
    older = serve_run(counters={"table_slots": 640, "live_blocks": 200, "live_tokens": 100},
                      pool_shapes=[(16, 368, 8, 128, 128)],
                      sizes={"hidden_size": 4096, "num_hidden_layers": 16},
                      trace=trace_of((CHUNK[-3:], "fwd_n32_t256_b20")))
    mla = serve_run(sizes=common.published_sizes(common.load_json(
        "configs", "deepseek-v2-serve-ep4-5l.json"), False), pool_shapes=[(5, 1024, 1, 128, 640)],
        counters={"live_tokens": 100}, trace=older.trace)
    parent = serve_run(counters={"live_tokens": 100}, trace=older.trace)  # this family, no counters
    for run in (older, mla, parent):
        for reader in (dsa_selected_share, dsa_attended_per_selected, dsa_indexer_share,
                       dsa_attention_roofline, dsa_index_roofline):
            assert reader.read(run) is None
    assert dsa_selected_share.read(types.SimpleNamespace(kind="serve")) is None  # no counters at all
    assert dsa_indexer_share.read(serve_run()) is None  # no trace
    assert not dsa_shapes.is_family(mla.sizes) and dsa_shapes.block_size([(3, 4)]) is None


@pytest.mark.reads_benchmark
def test_the_borrowed_readers_are_right_for_this_cell_and_the_others_are_not():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n8_t512_b132"), (CHUNK[-3:], "burst_n8_k16")))
    assert kv_write_share.read(run)[1]["calls"] == 2
    assert chunk_ms_per_ktok.read(run)[1]["chunk_programs_run"] == 1
    assert table_fill.read(run)[0] == pytest.approx(100 * 400 / 1024) and q_fill.read(run)[0] > 97
    # a pool of two unlike leaves: 640 + 128 values a token a layer at 2 bytes
    assert mla_pool_bytes_per_token.read(run)[0] == 1536.0
    # 16 held of 256: the router's width is a power-of-two multiple of the held count
    value, note = moe_held_ffn_share.read(run)
    assert note["grouped_matmul_s"] == pytest.approx(100e-3) and note["router_s"] == pytest.approx(1e-3)
    # left off the cell: mla_shapes counts EVERY causal pair, so a kernel that attended the
    # selection alone would read over 100%; it would read here, and read wrong
    assert mla_attention_roofline.read(run) is not None
    assert gdn_scan_share.read(run) is None
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    every = [w["name"] for w in bench["workloads"]]
    lists = {m["name"]: m.get("workloads", every) for m in bench["per_layer"]}
    for name in ("mla.attention_roofline", "mla.kernel_share", "paged_attention_roofline",
                 "pool.moved_share", "moe.ffn_share", "moe.expert_ffn_roofline", "moe.row_fill",
                 "conv.mixer_share", "conv.state_move_share", "conv.state_bytes_per_seq",
                 "step.burst_ms_per_step", "gdn.mixer_share", "gdn.scan_roofline", "gdn.scan_share",
                 "gdn.chunk_fill", "gdn.state_bytes_per_seq"):
        assert CELL not in lists[name], name
    for name in ("kv.write_share", "paged.table_fill", "paged.q_fill", "paged.slots_per_step",
                 "step.chunk_ms_per_ktok", "step.busy_ms_per_ktok", "sched.slot_fill", "sched.tokens_per_fwd",
                 "device.idle_share.serve", "serve.host_syncs_per_tok", "serve.compiles_in_window",
                 "setup.engine_init_s", "setup.trace_s", "setup.lower_s", "setup.load_s", "setup.programs",
                 "mla.pool_bytes_per_token", "moe.held_ffn_share", "dsa.selected_share",
                 "dsa.attended_per_selected", "dsa.indexer_share", "dsa.attention_roofline",
                 "dsa.index_roofline"):
        assert CELL in lists[name], name
    for name in ("dsa.selected_share", "dsa.attended_per_selected", "dsa.indexer_share",
                 "dsa.attention_roofline", "dsa.index_roofline"):
        assert lists[name] == [CELL], name
    ends = {m["name"]: m.get("workloads", every) for m in bench["end_to_end"]}
    assert CELL in ends["serve_tok_s"] and CELL in ends["ttft_p95_ms"] and CELL not in ends["tpot_p95_ms"]
