"""The cell ``serve.ssm-chat-burst``: its configuration's arithmetic, its six
``ssm.*`` readers (and the chunk-fill reader it borrows) on hand-built traces
and counters and at the rehearsal, the readers it borrows, and the readers that
would read and read wrong, which are left off it."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import common
from chipbench.generators.waves import Traffic
from chipbench.readers import (burst_ms_per_step, chunk_ms_per_ktok, conv_mixer_share,
                               conv_state_bytes_per_seq, gdn_chunk_fill, gdn_mixer_share,
                               gdn_state_bytes_per_seq, kv_write_share, moe_expert_ffn_roofline,
                               moe_held_ffn_share, moe_row_fill, paged_attention_roofline, q_fill,
                               ssm_mixer_share, ssm_scan_roofline, ssm_scan_share,
                               ssm_state_bytes_per_seq, ssm_state_move_share, ssm_update_roofline,
                               table_fill)
from chipbench.reduce import shapes, ssm_shapes, xplane
from chipbench.references import granite_moe_hybrid as ref
from tests.chipbench.conftest import ROOT

CONFIG, CELL = "granite-4.0-h-small-serve-ep2-10l", "serve.ssm-chat-burst"
POOL = [(1, 1024, 8, 128, 128), (9, 33, 3, 8448), (9, 33, 128, 64, 128)]
US = 1_000_000  # ns in the unit of the durations below (a millisecond)
CHUNK = [("%fusion.1 = bf16[1,512,16768]{2,1,0} fusion(...)", 60),           # u W_in
         ("%fusion.2 = bf16[1,512,8448]{2,1,0} fusion(...)", 10),            # the filter and its SiLU
         ("%fusion.3 = bf16[32,3,8448]{2,1,0} fusion(...)", 1),              # the shift's rows
         ("%fusion.4 = f32[32,128,64,128]{3,2,1,0} fusion(...)", 20),        # the matrices read from their slots
         ("%ssd_scan.5 = (bf16[128,2560,64]{2,1,0}, f32[32,128,64,128]{3,2,1,0}) custom-call(...)", 400),
         ("%scatter.6 = f32[297,128,64,128]{3,2,1,0} scatter(...)", 30),     # written back: the whole state's shape
         ("%fusion.7 = bf16[1,512,4096]{2,1,0} fusion(...)", 30),            # W_out, or any per-token op
         ("%kv_write.8 = (bf16[1024,8,128,128]{3,2,1,0}, bf16[1024,8,128,128]{3,2,1,0}) custom-call(...)", 3),
         ("%paged_attention.9 = bf16[8,2048,128]{2,1,0} custom-call(...)", 50),
         ("%gmm.10 = bf16[2816,768]{1,0} custom-call(...)", 100)]
DECODE = [("%fusion.11 = bf16[32,1,16768]{2,1,0} fusion(...)", 20),
          ("%fusion.12 = f32[32,128,64,128]{3,2,1,0} fusion(...)", 170),
          ("%ssd_update.13 = (f32[32,1,8192]{2,1,0}, f32[32,128,64,128]{3,2,1,0}) custom-call(...)", 180),
          ("%scatter.14 = f32[297,128,64,128]{3,2,1,0} scatter(...)", 175)] + CHUNK[-3:]


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
              "lengths": [300, 212], "max_new_tokens": 128, "prompt_tokens": 512,
              "counters": {"scan_chunks": 360, "scan_positions": 23040, "scan_live_positions": 4608,
                           "table_slots": 1024, "live_blocks": 400, "live_tokens": 512 + 64,
                           "attn_token_slots": 600, "token_slots": 576, "moe_routed_rows": 57600,
                           "moe_expert_rows": 28160},
              "pool_shapes": POOL, **fields}
    return types.SimpleNamespace(**fields)


def test_the_configuration_is_the_published_model_cut_to_one_chips_share():
    spec = common.load_json("configs", CONFIG + ".json")
    published = common.load_json("published", spec["published"] + ".json")["config"]
    assert sorted(spec["reduced"]) == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert all(spec[k] == v for k, v in published.items() if k not in spec["reduced"])
    assert (spec["num_hidden_layers"], spec["num_local_experts"], spec["vocab_size"]) == (10, 36, 50176)
    assert 2 * spec["num_local_experts"] == published["num_local_experts"]
    assert 2 * spec["vocab_size"] == published["vocab_size"] and len(spec["layer_types"]) == 40
    sizes = common.published_sizes(spec, False)
    assert ref.layer_kinds(sizes) == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert ref.segments(sizes) == [(0, 1, 5), (5, 1, 1), (6, 1, 4)]
    assert ref.router_width(sizes) == 72 and ref.EP_CHIPS == 2
    drawn = jax.eval_shape(lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.bfloat16))
    assert common.count_params(drawn) == 4_757_211_776  # the file's arithmetic: 9.51 GB in bf16
    assert drawn["experts"]["w_gate"].shape == (10, 36, 4096, 768)
    assert drawn["segments"][0][0]["moe"]["gate"]["wg"].shape == (5, 4096, 72)
    assert drawn["segments"][0][0]["mixer"]["w_in"].shape == (5, 4096, 16768)
    module, cfg = common.program_model(spec, sizes)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.head_dim) == (72, 36, 128)
    assert jax.tree_util.tree_structure(jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0)))) == jax.tree_util.tree_structure(drawn)
    engine = spec["engine"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, engine["num_blocks"], engine["block_size"], state_slots=32))  # the engine's default rows a step
    assert sorted({leaf.shape for leaf in jax.tree_util.tree_leaves(cache)}) == sorted(POOL)
    assert cache["state"]["ssm"].dtype == jnp.float32 and cache["state"]["conv"].dtype == jnp.bfloat16
    assert module.state_bytes_per_seq(cfg) == 38_204_928
    for name in ("expert_width", "head_dim", "router", "ssm_state", "conv_state", "scan_chunk", "norms",
                 "weights"):
        assert name in spec["assumed"], name
    wave = Traffic(common.load_json("traffic", "chat-burst.json")["params"], 1, spec["vocab_size"])
    assert len(wave.lengths) == 32 and max(wave.lengths) + 128 <= engine["max_blocks_per_seq"] * 128
    rehearsal = common.published_sizes(spec, True)
    assert ref.segments(rehearsal) == [(0, 1, 5), (5, 1, 1), (6, 1, 4)]
    assert ref.ssm_widths(rehearsal) == (8, 16, 16, 128, 160)


def test_the_ssm_readers_count_what_is_certain():
    run = serve_run()
    assert ssm_shapes.state_leaves(run.sizes, POOL) == (POOL[1], POOL[2])
    value, note = ssm_state_bytes_per_seq.read(run)
    assert value == 38_204_928 and note == {"conv": 9 * 50688, "ssm": 9 * 4194304}
    value, note = gdn_chunk_fill.read(run)  # the metric file ssm.chunk_fill names this reader
    assert value == 20.0 and note["chunks"] == 360
    assert common.load_json("metrics", "ssm.chunk_fill.json")["reader"] == "gdn_chunk_fill"
    traced = serve_run(trace=trace_of((CHUNK, "fwd_n32_t256_b20"), (DECODE, "burst_n32_k16")))
    value, note = ssm_mixer_share.read(traced)
    assert (note["in_proj_s"], note["filter_s"], note["state_s"], note["scan_s"], note["update_s"]) == \
        pytest.approx((80e-3, 11e-3, 395e-3, 400e-3, 180e-3))
    assert value == pytest.approx(100 * 1066e-3 / traced.trace.busy_s)
    value, note = ssm_scan_share.read(traced)
    assert value == pytest.approx(100 * 580e-3 / traced.trace.busy_s)
    assert (note["scan_s"], note["update_s"]) == (0.4, 0.18)
    # moves outside the kernels: two slot reads [32, 128, 64, 128], two writes of the whole state's shape
    value, note = ssm_state_move_share.read(traced)
    assert value == pytest.approx(100 * 395e-3 / traced.trace.busy_s)
    assert (note["whole_state_s"], note["whole_state_operations"], note["row_operations"]) == (0.205, 2, 2)
    # the scan's least time: 4,608 positions x 128 heads x 4 x 64 x 128 operations; 33,792 B a position
    least = ssm_shapes.scan_least_seconds(run.sizes, 4608, run.peaks)
    assert least["compute_s"] == pytest.approx(4608 * 128 * 32768 / 197e12)
    assert least["memory_s"] == pytest.approx(4608 * 33792 / 819e9)
    value, note = ssm_scan_roofline.read(traced)
    assert value == pytest.approx(100 * least["seconds"] / 0.4) and 0 < value < 100
    assert note["mostly"] == "memory_s"
    # the update's: 576 live tokens less the 512 the scans took = 64 decode rows, in 9 layers
    value, note = ssm_update_roofline.read(traced)
    assert note["row_updates"] == 64 * 9
    assert value == pytest.approx(100 * (64 * 9 * 2 * 4194304 / 819e9) / 0.18) and 0 < value < 100


def test_a_program_without_the_family_gives_nothing_and_does_not_raise():
    """What the parent commit, and every other configuration, hands these
    readers: no such leaves, no such kernel, no such keys."""
    older = serve_run(counters={"table_slots": 640, "live_blocks": 200}, pool_shapes=[(16, 368, 8, 128, 128)],
                      sizes={"hidden_size": 4096, "num_hidden_layers": 16},
                      trace=trace_of((CHUNK[-3:], "fwd_n32_t256_b20")))
    qwen = serve_run(sizes=common.published_sizes(common.load_json(
        "configs", "qwen3-next-80b-a3b-serve-ep4.json"), False),
        pool_shapes=[(3, 800, 2, 128, 256), (9, 9, 3, 8192), (9, 9, 32, 128, 128)], trace=older.trace)
    for run in (older, qwen, serve_run(counters={}, trace=older.trace)):
        for reader in (ssm_scan_share, ssm_scan_roofline, ssm_update_roofline, ssm_state_move_share,
                       ssm_mixer_share):
            assert reader.read(run) is None, reader.__name__
    for run in (older, qwen):
        assert ssm_state_bytes_per_seq.read(run) is None
    assert ssm_update_roofline.read(types.SimpleNamespace(kind="serve", sizes={}, trace=None)) is None


def test_the_ssm_counters_are_read_at_the_rehearsal(rehearse):
    got = rehearse("--workload", CELL, "--seed", str(2 ** 31 + 77), "--seconds", "0", "--trace", "1")
    assert got.code == 3 and got.line["would_be_correct"] is True
    # nine Mamba-2 layers of 8 heads of 16 x 16 in float32, and 3 rows of 160 columns in bfloat16
    assert got.line["metrics"]["ssm.state_bytes_per_seq"]["value"] == 9 * (8 * 16 * 16 * 4 + 3 * 160 * 2)
    assert 0 < got.line["metrics"]["ssm.chunk_fill"]["value"] <= 100
    assert not any(name.startswith("gdn.") or name.startswith("conv.") for name in got.line["metrics"])


@pytest.mark.reads_benchmark
def test_the_borrowed_readers_are_right_for_this_cell_and_the_others_are_not():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n32_t256_b20"), (DECODE, "burst_n32_k16")),
                    forwards=17, stepwise_forwards=1)
    assert kv_write_share.read(run)[1]["calls"] == 2
    assert chunk_ms_per_ktok.read(run)[1]["chunk_programs_run"] == 1
    assert burst_ms_per_step.read(run)[0] == pytest.approx(6000 / 16)  # one burst of 16 steps
    assert table_fill.read(run)[0] == pytest.approx(100 * 400 / 1024) and q_fill.read(run)[0] == 96.0
    # left off the cell: the attention counts take every one of the 10 layers for an attention layer
    # (one is): per token 10 x 8 KV heads x 128 x 2 x 2 B where the pool holds a tenth of it
    assert paged_attention_roofline.read(run) is not None  # it would read, and read wrong:
    assert shapes.kv_bytes_per_token(run.sizes) == 10 * 4096
    # the expert readers: one asks for num_experts, the other for DeepSeek-V2's keys, and find nothing;
    # the row fill counts the half of the picks that are held elsewhere among its rows (over 100%)
    assert "num_experts" not in run.sizes and "n_routed_experts" not in run.sizes
    assert moe_held_ffn_share.read(run) is None and moe_expert_ffn_roofline.read(serve_run()) is None
    assert moe_row_fill.read(run)[0] > 200
    # the other two state families' readers find none of their keys or leaves
    assert conv_state_bytes_per_seq.read(run) is None and conv_mixer_share.read(run) is None
    assert gdn_state_bytes_per_seq.read(run) is None and gdn_mixer_share.read(run) is None
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    every = [w["name"] for w in bench["workloads"]]  # an entry without a list is read in every cell
    lists = {m["name"]: m.get("workloads", every) for m in bench["per_layer"]}
    for name in ("paged_attention_roofline", "pool.moved_share", "moe.ffn_share", "moe.expert_ffn_roofline",
                 "moe.held_ffn_share", "moe.row_fill", "conv.mixer_share", "conv.state_move_share",
                 "conv.state_bytes_per_seq", "gdn.mixer_share", "gdn.scan_roofline", "gdn.scan_share",
                 "gdn.chunk_fill", "gdn.state_bytes_per_seq", "mla.attention_roofline"):
        assert CELL not in lists[name], name
    for name in ("kv.write_share", "paged.table_fill", "paged.q_fill", "paged.slots_per_step",
                 "step.chunk_ms_per_ktok", "step.busy_ms_per_ktok", "step.burst_ms_per_step",
                 "sched.slot_fill", "sched.tokens_per_fwd", "device.idle_share.serve",
                 "serve.host_syncs_per_tok", "serve.compiles_in_window", "setup.engine_init_s",
                 "setup.trace_s", "setup.lower_s", "setup.load_s", "setup.programs", "ssm.mixer_share",
                 "ssm.scan_roofline", "ssm.update_roofline", "ssm.scan_share", "ssm.state_move_share",
                 "ssm.chunk_fill", "ssm.state_bytes_per_seq"):
        assert lists[name] == [CELL] or CELL in lists[name], name
    assert all(lists[name] == [CELL] for name in lists if name.startswith("ssm."))
    ends = {m["name"]: m.get("workloads", every) for m in bench["end_to_end"]}
    assert all(CELL in ends[name] for name in ("serve_tok_s", "ttft_p95_ms", "tpot_p95_ms"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "chat-burst", 1)
