"""The cell ``serve.gdn-long-prompt``: its configuration's arithmetic, its five
``gdn.*`` readers on hand-built traces and counters, the readers it borrows, and
the readers that would read and read wrong, which are left off it."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import common
from chipbench.generators.waves import Traffic
from chipbench.readers import (chunk_ms_per_ktok, conv_mixer_share, conv_state_bytes_per_seq,
                               gdn_chunk_fill, gdn_mixer_share, gdn_scan_roofline, gdn_scan_share,
                               gdn_state_bytes_per_seq, kv_write_share, moe_expert_ffn_roofline,
                               moe_held_ffn_share, paged_attention_roofline, q_fill, table_fill)
from chipbench.reduce import gdn_shapes, shapes, xplane
from chipbench.references import qwen3_next as ref
from tests.chipbench.conftest import ROOT

CONFIG, CELL = "qwen3-next-80b-a3b-serve-ep4", "serve.gdn-long-prompt"
POOL = [(3, 800, 2, 128, 256), (9, 9, 3, 8192), (9, 9, 32, 128, 128)]
US = 1_000_000  # ns in the unit of the durations below (a millisecond)
CHUNK = [("%fusion.1 = bf16[1,2048,12288]{2,1,0} fusion(...)", 60),          # u W_qkvz
         ("%fusion.2 = f32[1,2048,8192]{2,1,0} fusion(...)", 10),            # the filter and its SiLU
         ("%fusion.3 = bf16[8,3,8192]{2,1,0} fusion(...)", 1),               # the shift's rows
         ("%fusion.4 = f32[8,32,128,128]{3,2,1,0} fusion(...)", 2),          # the matrices read
         ("%gdn_scan.5 = (bf16[32,2560,128]{2,1,0}, f32[8,32,128,128]{3,2,1,0}) custom-call(...)", 400),
         ("%fusion.6 = bf16[1,2048,2048]{2,1,0} fusion(...)", 30),           # W_out, or any per-token op
         ("%kv_write.7 = (bf16[2400,2,128,256]{3,2,1,0}, bf16[2400,2,128,256]{3,2,1,0}) custom-call(...)", 3),
         ("%paged_attention.8 = bf16[2,16384,256]{2,1,0} custom-call(...)", 150),
         ("%gmm.9 = bf16[20480,512]{1,0} custom-call(...)", 100)]


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
              "lengths": [8875, 15701], "max_new_tokens": 32, "prompt_tokens": 24576,
              "counters": {"scan_chunks": 360, "scan_positions": 23040, "scan_live_positions": 18432,
                           "table_slots": 1024, "live_blocks": 400, "live_tokens": 2048,
                           "attn_token_slots": 2104, "token_slots": 2048},
              "pool_shapes": POOL, **fields}
    return types.SimpleNamespace(**fields)


def test_the_configuration_is_the_published_model_cut_to_one_chips_share():
    spec = common.load_json("configs", CONFIG + ".json")
    published = common.load_json("published", spec["published"] + ".json")["config"]
    assert sorted(spec["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert all(spec[k] == v for k, v in published.items() if k not in spec["reduced"])
    assert (spec["num_hidden_layers"], spec["num_experts"], spec["vocab_size"]) == (12, 128, 37984)
    assert 4 * spec["num_experts"] == published["num_experts"] and 4 * spec["vocab_size"] == published["vocab_size"]
    sizes = common.published_sizes(spec, False)
    assert ref.layer_kinds(sizes) == (["linear_attention"] * 3 + ["full_attention"]) * 3
    assert ref.segments(sizes) == [(0, 4, 3)] and ref.router_width(sizes) == 512 and ref.EP_CHIPS == 4
    drawn = jax.eval_shape(lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.bfloat16))
    assert common.count_params(drawn) == 5_423_084_736  # the file's arithmetic: 10.85 GB in bf16
    assert drawn["experts"]["w_gate"].shape == (12, 128, 2048, 512)
    assert drawn["segments"][0][0]["moe"]["gate"]["wg"].shape == (3, 2048, 512)
    module, cfg = common.program_model(spec, sizes)
    assert jax.tree_util.tree_structure(jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0)))) == jax.tree_util.tree_structure(drawn)
    engine = spec["engine"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, engine["num_blocks"], engine["block_size"], state_slots=engine["max_seqs_per_step"]))
    assert sorted({leaf.shape for leaf in jax.tree_util.tree_leaves(cache)}) == sorted(POOL)
    assert cache["state"]["recurrent"].dtype == jnp.float32 and cache["k"].dtype == jnp.bfloat16
    assert module.state_bytes_per_seq(cfg) == 19_316_736
    for name in ("layer_types", "norms", "qkvz_layout", "l2norm_eps", "recurrent_state", "conv_state",
                 "mtp", "weights"):
        assert name in spec["assumed"], name
    wave = Traffic(common.load_json("traffic", "gdn-long-prompt.json")["params"], 1, spec["vocab_size"])
    assert sum(wave.lengths) == 73728 and len(wave.lengths) <= engine["max_seqs_per_step"]
    rehearsal = common.published_sizes(spec, True)
    assert set(ref.layer_kinds(rehearsal)) == {"linear_attention", "full_attention"}
    assert ref.segments(rehearsal) == [(0, 4, 2)]


def test_the_gdn_readers_count_what_is_certain():
    run = serve_run()
    assert gdn_shapes.state_leaves(run.sizes, POOL) == (POOL[1], POOL[2])
    value, note = gdn_state_bytes_per_seq.read(run)
    assert value == 19_316_736 and note == {"conv": 9 * 49152, "recurrent": 9 * 2097152}
    value, note = gdn_chunk_fill.read(run)
    assert value == 80.0 and note["chunks"] == 360
    traced = serve_run(trace=trace_of((CHUNK, "fwd_n8_t2048_b132"), (CHUNK[-3:], "burst_n8_k16")))
    value, note = gdn_mixer_share.read(traced)
    assert (note["in_proj_s"], note["filter_s"], note["state_s"], note["scan_s"]) == \
        pytest.approx((60e-3, 11e-3, 2e-3, 400e-3))
    assert value == pytest.approx(100 * 473e-3 / traced.trace.busy_s)
    value, note = gdn_scan_share.read(traced)
    assert value == pytest.approx(100 * 400e-3 / traced.trace.busy_s) and note["kernel_s"] == 0.4
    # the least time: 18,432 positions = 288 chunks x 32 heads x 9.96 MFLOP; 24,832 B a position
    least = gdn_shapes.scan_least_seconds(run.sizes, 18432, run.peaks)
    assert least["compute_s"] == pytest.approx(288 * 32 * 9_961_472 / 197e12)
    assert least["memory_s"] == pytest.approx(18432 * 24832 / 819e9)
    value, note = gdn_scan_roofline.read(traced)
    assert value == pytest.approx(100 * least["seconds"] / 0.4) and 0 < value < 100
    assert note["mostly"] == "memory_s"


def test_a_program_without_the_family_gives_nothing_and_does_not_raise():
    """What the parent commit, and every other configuration, hands these
    readers: no scan counters, no such leaves, no such kernel."""
    older = serve_run(counters={"table_slots": 640, "live_blocks": 200}, pool_shapes=[(16, 368, 8, 128, 128)],
                      sizes={"hidden_size": 4096, "num_hidden_layers": 16},
                      trace=trace_of((CHUNK[-3:], "fwd_n32_t256_b20")))
    lfm2 = serve_run(sizes=common.published_sizes(common.load_json(
        "configs", "lfm2-24b-a2b-serve-10l.json"), False), pool_shapes=[(2, 1024, 4, 128, 128), (8, 33, 2, 2048)],
        counters={}, trace=older.trace)
    for run in (older, lfm2, serve_run(counters={}, trace=older.trace)):
        for reader in (gdn_chunk_fill, gdn_scan_share, gdn_scan_roofline):
            assert reader.read(run) is None
    for run in (older, lfm2):
        assert gdn_mixer_share.read(run) is None and gdn_state_bytes_per_seq.read(run) is None
    assert gdn_chunk_fill.read(types.SimpleNamespace(kind="serve")) is None  # no counters at all


@pytest.mark.reads_benchmark
def test_the_borrowed_readers_are_right_for_this_cell_and_the_others_are_not():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n8_t2048_b132"), (CHUNK[-3:], "burst_n8_k16")))
    assert kv_write_share.read(run)[1]["calls"] == 2
    assert chunk_ms_per_ktok.read(run)[1]["chunk_programs_run"] == 1
    assert table_fill.read(run)[0] == pytest.approx(100 * 400 / 1024) and q_fill.read(run)[0] > 97
    # left off the cell: the attention counts take every one of the 12 layers for an attention layer
    # (three are): per token 12 x 2 KV heads x 256 x 2 x 2 B where the pool holds a quarter of it
    assert paged_attention_roofline.read(run) is not None  # it would read, and read wrong:
    assert shapes.kv_bytes_per_token(run.sizes) == 4 * 6144
    # the expert readers: one takes the dense width (5120) for an expert's (512), the other asks for
    # DeepSeek-V2's keys and finds nothing; LFM2's conv readers find no conv_L_cache
    assert run.sizes["intermediate_size"] == 5120 != run.sizes["moe_intermediate_size"]
    assert moe_held_ffn_share.read(run) is None and moe_expert_ffn_roofline.read(serve_run()) is None
    assert conv_state_bytes_per_seq.read(run) is None and conv_mixer_share.read(run) is None
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    every = [w["name"] for w in bench["workloads"]]  # an entry without a list is read in every cell
    lists = {m["name"]: m.get("workloads", every) for m in bench["per_layer"]}
    for name in ("paged_attention_roofline", "pool.moved_share", "moe.ffn_share", "moe.expert_ffn_roofline",
                 "moe.held_ffn_share", "moe.row_fill", "conv.mixer_share", "conv.state_move_share",
                 "conv.state_bytes_per_seq", "step.burst_ms_per_step", "mla.attention_roofline"):
        assert CELL not in lists[name], name
    for name in ("kv.write_share", "paged.table_fill", "paged.q_fill", "paged.slots_per_step",
                 "step.chunk_ms_per_ktok", "step.busy_ms_per_ktok", "sched.slot_fill", "sched.tokens_per_fwd",
                 "device.idle_share.serve", "serve.host_syncs_per_tok", "serve.compiles_in_window",
                 "setup.engine_init_s", "setup.trace_s", "setup.lower_s", "setup.load_s", "setup.programs",
                 "gdn.mixer_share", "gdn.scan_roofline", "gdn.scan_share", "gdn.chunk_fill",
                 "gdn.state_bytes_per_seq"):
        assert CELL in lists[name], name
    ends = {m["name"]: m.get("workloads", every) for m in bench["end_to_end"]}
    assert CELL in ends["serve_tok_s"] and CELL in ends["ttft_p95_ms"] and CELL not in ends["tpot_p95_ms"]
