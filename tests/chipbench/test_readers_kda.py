"""The cell ``serve.kda-mixed-lengths``: its configuration's arithmetic, its
``kda.*`` readers (and the chunk-fill reader it borrows) on hand-built traces and
counters and at the rehearsal, what a program without the family hands them (the
parent commit: nothing, and no raise), the readers the cell borrows, and the readers
that would read and read wrong, which are left off it."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import common
from chipbench.generators.waves import Traffic
from chipbench.readers import (gdn_chunk_fill, gdn_state_bytes_per_seq, kda_scan_roofline,
                               kda_state_bytes_per_seq, kda_update_roofline, mla_attention_roofline,
                               mla_kernel_share, mla_pool_bytes_per_token, moe_row_fill,
                               paged_attention_roofline, ssm_scan_roofline, ssm_state_bytes_per_seq,
                               ssm_update_roofline, table_fill)
from chipbench.reduce import kda_shapes, mla_shapes, xplane
from chipbench.references import bailing_hybrid as ref
from tests.chipbench.conftest import ROOT

CONFIG, CELL = "ling-3.0-flash-serve-ep8-12l", "serve.kda-mixed-lengths"
SPEC = common.load_json("configs", CONFIG + ".json")
POOL = [(2, 1024, 1, 128, 640), (10, 17, 3, 12288), (10, 17, 32, 128, 128)]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns
CHUNK = [("%fusion.1 = bf16[1,1024,12288]{2,1,0} fusion(...)", 60),            # u [W_q | W_k | W_v]
         ("%kda_update.2 = (f32[16,32,1,128]{3,2,1,0}, f32[170,32,128,128]{3,2,1,0}) custom-call(...)", 4),
         ("%kda_scan.3 = (bf16[32,1280,128]{2,1,0}, f32[170,32,128,128]{3,2,1,0}) custom-call(...)", 90),
         ("%kv_write.4 = bf16[2048,1,128,640]{3,2,1,0} custom-call(...)", 2),
         ("%paged_attention.5 = bf16[1,32768,512]{2,1,0} custom-call(...)", 50),
         ("%gmm.6 = bf16[2176,768]{1,0} custom-call(...)", 40)]
DECODE = [("%kda_update.7 = (f32[16,32,1,128]{3,2,1,0}, f32[170,32,128,128]{3,2,1,0}) custom-call(...)", 1)
          ] + CHUNK[-3:]


def trace_of(*programs):
    ops, modules = [], []
    for i, (body, name) in enumerate(programs):
        t = 10_000 * MS * i
        modules.append((f"jit_{name}(1)", t, 6000 * MS))
        for op, ms in body:
            ops.append((xplane.short_name(op), t, ms * MS))
            t += ms * MS
    return xplane.Reduction({"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": []})


def serve_run(**fields):
    wave = Traffic(common.load_json("traffic", "mixed-lengths.json")["params"], 1, SPEC["vocab_size"])
    fields = {"kind": "serve", "trace": None, "sizes": common.published_sizes(SPEC, False),
              "peaks": PEAKS, "lengths": wave.lengths, "max_new_tokens": wave.max_new_tokens,
              "counters": {"scan_chunks": 2000, "scan_positions": 128000, "scan_live_positions": 10 * 9000,
                           "live_tokens": 9000 + 384, "table_slots": 2560, "live_blocks": 800,
                           "moe_routed_rows": 9384 * 8 * 10, "moe_expert_rows": 1280 * 10 * 9},
              "pool_shapes": POOL, **fields}
    return types.SimpleNamespace(**fields)


def test_the_program_takes_the_configuration_and_holds_the_cache_it_says():
    """(The cut against the published row: ``test_reference_bailing_hybrid.py``.)"""
    sizes = common.published_sizes(SPEC, False)
    module, cfg = common.program_model(SPEC, sizes)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_k_dense, cfg.num_layers) == (512, 64, 2, 12)
    drawn = jax.eval_shape(lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.bfloat16))
    assert jax.tree_util.tree_structure(jax.eval_shape(
        lambda: module.init_params(cfg, jax.random.PRNGKey(0)))) == jax.tree_util.tree_structure(drawn)
    engine = SPEC["engine"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, engine["num_blocks"], engine["block_size"], state_slots=engine["max_seqs_per_step"]))
    assert sorted({leaf.shape for leaf in jax.tree_util.tree_leaves(cache)}) == sorted(POOL)
    assert cache["state"]["recurrent"].dtype == jnp.float32 and cache["state"]["conv"].dtype == jnp.bfloat16
    assert module.state_bytes_per_seq(cfg) == 21_708_800
    for name in ("layer_kinds", "qk_norm", "kda_gate", "output_gate", "kda_heads", "mla", "router", "norms",
                 "recurrent_state", "conv_state", "scan_chunk", "left_out", "weights"):
        assert name in SPEC["assumed"], name
    assert "eight" in SPEC["deployment"] and "group 0" in SPEC["deployment"]
    rehearsal = common.published_sizes(SPEC, True)
    assert ref.router_width(rehearsal) == 32 and len(ref.layer_kinds(rehearsal)) == 6


def test_the_kda_readers_count_what_is_certain():
    run = serve_run()
    assert kda_shapes.state_leaves(run.sizes, POOL) == (POOL[1], POOL[2])
    value, note = kda_state_bytes_per_seq.read(run)
    assert value == 21_708_800 and note == {"conv": 10 * 73728, "recurrent": 10 * 2097152}
    value, note = gdn_chunk_fill.read(run)  # the metric file kda.chunk_fill names this reader
    assert value == pytest.approx(100 * 90000 / 128000) and note["chunks"] == 2000
    assert common.load_json("metrics", "kda.chunk_fill.json")["reader"] == "gdn_chunk_fill"
    traced = serve_run(trace=trace_of((CHUNK, "fwd_n16_t1024_b32"), (DECODE, "burst_n16_k31_b32")))
    # the scan's least time: 90,000 positions x 32 heads x 6 x 128 x 128 operations; 49,280 B a position
    least = kda_shapes.scan_least_seconds(run.sizes, 90000, PEAKS)
    assert least["compute_s"] == pytest.approx(90000 * 32 * 6 * 16384 / 197e12)
    assert least["memory_s"] == pytest.approx(90000 * (4 * 4096 * 2 + 4096 * 4 + 32 * 4) / 819e9)
    value, note = kda_scan_roofline.read(traced)
    assert value == pytest.approx(100 * least["seconds"] / 0.09) and 0 < value < 100
    assert note["mostly"] == "memory_s" and note["kernel_s"] == 0.09
    # the update's: 9,384 live tokens less the 9,000 the scans walked = 384 rows of one token, in 10 layers
    value, note = kda_update_roofline.read(traced)
    assert note["row_updates"] == 384 * 10 and note["kernel_s"] == 0.005
    assert value == pytest.approx(100 * (3840 * 2 * 2097152 / 819e9) / 0.005) and 0 < value < 100 * 4
    # the latent kernel's share of busy time is right whatever the number of latent layers
    value, note = mla_kernel_share.read(traced)
    assert value == pytest.approx(100 * 0.1 / traced.trace.busy_s) and note["kernel_s"] == 0.1


def test_a_program_without_the_family_gives_nothing_and_does_not_raise():
    """What the parent commit, and every other configuration, hands these readers: no such
    leaves, no such kernel, no such keys."""
    there = trace_of((CHUNK[-3:], "fwd_n16_t1024_b32"))
    mistral = serve_run(counters={"table_slots": 640, "live_blocks": 200}, pool_shapes=[(16, 368, 8, 128, 128)],
                        sizes={"hidden_size": 4096, "num_hidden_layers": 16}, trace=there)
    qwen = serve_run(sizes=common.published_sizes(common.load_json(
        "configs", "qwen3-next-80b-a3b-serve-ep4.json"), False),
        pool_shapes=[(3, 800, 2, 128, 256), (9, 9, 3, 8192), (9, 9, 32, 128, 128)], trace=there)
    for run in (mistral, qwen, serve_run(counters={}, trace=there),
                serve_run(trace=there)):  # this configuration under a program that has no such kernels
        for reader in (kda_scan_roofline, kda_update_roofline):
            assert reader.read(run) is None, reader.__name__
    for run in (mistral, qwen, types.SimpleNamespace(kind="train", trace=None, sizes={})):
        assert kda_state_bytes_per_seq.read(run) is None
    assert kda_update_roofline.read(types.SimpleNamespace(kind="serve", sizes={}, trace=None)) is None


def test_the_kda_counters_are_read_at_the_rehearsal(rehearse):
    got = rehearse("--workload", CELL, "--seed", str(2 ** 31 + 77), "--seconds", "0", "--trace", "1")
    assert got.code == 3 and got.line["would_be_correct"] is True
    # four KDA layers of 2 heads of 32 x 32 in float32, and 3 rows of 192 columns in bfloat16
    assert got.line["metrics"]["kda.state_bytes_per_seq"]["value"] == 4 * (2 * 32 * 32 * 4 + 3 * 192 * 2)
    assert 0 < got.line["metrics"]["kda.chunk_fill"]["value"] <= 100
    assert not any(name.split(".")[0] in ("gdn", "ssm", "conv") for name in got.line["metrics"])


@pytest.mark.reads_benchmark
def test_the_borrowed_readers_are_right_for_this_cell_and_the_others_are_not():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n16_t1024_b32"), (DECODE, "burst_n16_k31_b32")))
    assert table_fill.read(run)[0] == pytest.approx(100 * 800 / 2560)
    # left off the cell, each shown wrong or empty here:
    # the latent readers take every one of the 12 layers for a latent layer (two are): six times the work
    two = mla_shapes.attention_least_seconds({**run.sizes, "num_hidden_layers": 2}, run.lengths, 32, PEAKS)
    assert mla_attention_roofline.read(run)[1]["seconds"] == pytest.approx(6 * two["seconds"], rel=1e-3)
    # the pool's bytes a token count the rank-5 state leaf [10, 17, 32, 128, 128] as a pool leaf
    assert mla_pool_bytes_per_token.read(run)[0] == 2 * (640 + 32 * 128) != 2 * 640
    assert paged_attention_roofline.read(run) is not None  # per-head K and V: 12 layers of 32 heads
    # the row fill counts the seven eighths of the picks that are held elsewhere among its rows
    assert moe_row_fill.read(run)[0] > 500
    # the other state families' readers find none of their keys, leaves or kernels
    assert gdn_state_bytes_per_seq.read(run) is None and ssm_state_bytes_per_seq.read(run) is None
    assert ssm_scan_roofline.read(run) is None and ssm_update_roofline.read(run) is None
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    every = [w["name"] for w in bench["workloads"]]  # an entry without a list is read in every cell
    lists = {m["name"]: m.get("workloads", every) for m in bench["per_layer"]}
    for name in ("paged_attention_roofline", "pool.moved_share", "moe.ffn_share", "moe.expert_ffn_roofline",
                 "moe.held_ffn_share", "moe.row_fill", "mla.attention_roofline", "mla.pool_bytes_per_token",
                 "step.burst_ms_per_step", "gdn.scan_roofline", "gdn.chunk_fill", "gdn.state_bytes_per_seq",
                 "ssm.scan_roofline", "ssm.update_roofline", "ssm.chunk_fill", "ssm.state_move_share",
                 "dsa.attention_roofline", "swa.attention_roofline"):
        assert CELL not in lists[name], name
    for name in ("kv.write_share", "paged.table_fill", "paged.q_fill", "paged.slots_per_step",
                 "step.chunk_ms_per_ktok", "step.busy_ms_per_ktok", "sched.slot_fill",
                 "sched.tokens_per_fwd", "device.idle_share.serve", "serve.host_syncs_per_tok",
                 "serve.compiles_in_window", "setup.engine_init_s", "setup.trace_s", "setup.lower_s",
                 "setup.load_s", "setup.programs", "scope.attributed_share", "scope.attention_share",
                 "scope.expert_share", "scope.mixer_share", "scope.dense_ffn_share", "scope.head_share",
                 "mla.kernel_share"):
        assert CELL in lists[name], name
    mine = ("kda.scan_roofline", "kda.update_roofline", "kda.chunk_fill", "kda.state_bytes_per_seq")
    assert all(lists[name] == [CELL] for name in mine)
    for name in mine:  # the metric file and the benchmark's entry agree, found by name
        spec = common.load_json("metrics", name + ".json")
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert all(spec[k] == entry[k] for k in ("unit", "better", "source", "layer", "moves"))
        assert entry["moves"] == "serve_tok_s"
    ends = {m["name"]: m.get("workloads", every) for m in bench["end_to_end"]}
    assert CELL in ends["serve_tok_s"] and CELL in ends["ttft_p95_ms"] and CELL not in ends["tpot_p95_ms"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "mixed-lengths", 1)
