"""The cell ``serve.nemotron-decode-wide``: its configuration's arithmetic, its
``ssmg.*`` and ``relu2.*`` readers on hand-built traces and counters (the rehearsal of
the cell is ``test_harness.py``'s, traced), what a program without the family hands them (the parent commit:
nothing, and no raise), the readers the cell borrows, and the readers that would
read and read wrong, which are left off it."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

from chipbench import common
from chipbench.generators.waves import Traffic
from chipbench.readers import (gdn_chunk_fill, gdn_state_bytes_per_seq, kda_state_bytes_per_seq,
                               mla_pool_bytes_per_token, moe_row_fill, paged_attention_roofline,
                               relu2_expert_ffn_roofline, scmoe_expert_ffn_roofline, scmoe_held_row_fill,
                               ssm_scan_roofline, ssm_state_bytes_per_seq, ssm_update_roofline,
                               ssmg_scan_roofline, ssmg_state_bytes_per_seq, ssmg_update_roofline, table_fill)
from chipbench.reduce import nemotron_h_shapes as shapes
from chipbench.reduce import xplane
from chipbench.references import nemotron_h as ref
from tests.chipbench.conftest import ROOT

CONFIG, CELL = "nemotron-3-nano-30b-a3b-serve-ep2-14l", "serve.nemotron-decode-wide"
SPEC = common.load_json("configs", CONFIG + ".json")
POOL = [(3, ), (2, 1024, 2, 128, 128), (6, 65, 3, 6144), (6, 65, 64, 64, 128)]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns
CHUNK = [("%fusion.1 = bf16[1,1024,10304]{2,1,0} fusion(...)", 30),             # u W_in
         ("%ssd_update.2 = (f32[64,1,4096]{2,1,0}, f32[390,64,64,128]{3,2,1,0}) custom-call(...)", 3),
         ("%ssd_scan.3 = (bf16[64,1280,64]{2,1,0}, f32[390,64,64,128]{3,2,1,0}) custom-call(...)", 12),
         ("%kv_write.4 = bf16[2048,2,128,128]{3,2,1,0} custom-call(...)", 1),
         ("%paged_attention.5 = bf16[1,32768,128]{2,1,0} custom-call(...)", 8),
         ("%gmm.6 = bf16[3840,1856]{1,0} custom-call(...)", 40), ("%gmm.7 = bf16[3840,2688]{1,0} custom-call(...)", 38)]
DECODE = [("%ssd_update.8 = (f32[64,1,4096]{2,1,0}, f32[390,64,64,128]{3,2,1,0}) custom-call(...)", 2),
          ("%gmm.9 = bf16[256,1856]{1,0} custom-call(...)", 9), ("%gmm.10 = bf16[256,2688]{1,0} custom-call(...)", 9)]


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
    wave = Traffic(common.load_json("traffic", "scmoe-decode-wide.json")["params"], 1, SPEC["vocab_size"])
    live = 20000 + 64 * 256
    fields = {"kind": "serve", "trace": None, "sizes": common.published_sizes(SPEC, False),
              "peaks": PEAKS, "lengths": wave.lengths, "max_new_tokens": wave.max_new_tokens,
              "counters": {"scan_chunks": 3000, "scan_positions": 192000, "scan_live_positions": 6 * 19900,
                           "live_tokens": live, "table_slots": 2560, "live_blocks": 800,
                           "moe_routed_rows": live * 6 * 6, "moe_expert_rows": 6 * (20 * 3840 + 256 * 256),
                           "moe_held_picks": live * 6 * 3, "moe_experts_hit": 6 * (20 * 64 + 256 * 40)},
              "pool_shapes": POOL, **fields}
    return types.SimpleNamespace(**fields)


def test_the_program_takes_the_configuration_and_holds_the_cache_it_says():
    sizes = common.published_sizes(SPEC, False)
    module, cfg = common.program_model(SPEC, sizes)
    assert (cfg.num_experts, cfg.held_experts, cfg.num_layers, cfg.kinds) == (128, 64, 14, "MEMEM*EMEMEM*E")
    assert ref.router_width(sizes) == cfg.num_experts and ref.layer_kinds(sizes) == cfg.kinds
    drawn = jax.eval_shape(lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.bfloat16))
    own = jax.eval_shape(lambda: module.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    assert jax.tree_util.tree_structure(own) == jax.tree_util.tree_structure(drawn)
    assert [a.shape for a in jax.tree_util.tree_leaves(own)] == [a.shape for a in jax.tree_util.tree_leaves(drawn)]
    assert common.count_params(drawn) == 4_584_903_936  # 9.17 GB at 2 bytes: the file's reduced arithmetic
    assert "4,584,903,936" in SPEC["reduced"]["num_hidden_layers"]
    engine = SPEC["engine"]
    assert engine["max_seqs_per_step"] == 64 and engine["block_size"] * engine["max_blocks_per_seq"] >= 512 + 256
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, engine["num_blocks"], engine["block_size"], state_slots=engine["max_seqs_per_step"]))
    # no state row and no pool row for an E layer: [6, 65, ...] and [2, 1024, ...] for 14 layers
    assert sorted({leaf.shape for leaf in jax.tree_util.tree_leaves(cache)}) == sorted(POOL)
    assert cache["state"]["ssm"].dtype == jnp.float32 and cache["state"]["conv"].dtype == jnp.bfloat16
    assert module.state_bytes_per_seq(cfg) == 12_804_096
    for name in ("no_rotary", "inner_width", "grouped_norm", "dt", "router", "intermediate_size",
                 "rescale_prenorm_residual", "ssm_state", "conv_state", "scan_chunk", "weights"):
        assert name in SPEC["assumed"], name
    assert "two v5e chips" in SPEC["deployment"] and "experts 0..63" in SPEC["deployment"]
    assert "rows 0..65,535" in SPEC["deployment"]
    rehearsal = common.published_sizes(SPEC, True)
    assert ref.router_width(rehearsal) == 8 and ref.ssm_widths(rehearsal)[:4] == (8, 16, 16, 4)
    traffic = common.load_json("traffic", "scmoe-decode-wide.json")["params"]
    assert (traffic["requests_per_wave"], traffic["max_new_tokens"]) == (64, 256)


def test_the_readers_count_what_is_certain():
    run = serve_run()
    assert shapes.state_leaves(run.sizes, POOL) == (POOL[2], POOL[3])
    value, note = ssmg_state_bytes_per_seq.read(run)
    assert value == 12_804_096 and note == {"conv": 6 * 36864, "ssm": 6 * 2097152}
    traced = serve_run(trace=trace_of((CHUNK, "fwd_n64_t512_b8"), (DECODE, "burst_n64_k16_b8")))
    # the scan's least time: 4 P N operations a token a head; x and y at 4096, B and C at EIGHT groups of 128
    least = shapes.scan_least_seconds(run.sizes, 6 * 19900, PEAKS)
    assert least["compute_s"] == pytest.approx(6 * 19900 * 64 * 4 * 64 * 128 / 197e12)
    assert least["memory_s"] == pytest.approx(6 * 19900 * (2 * 4096 * 2 + 2 * 8 * 128 * 2 + 64 * 4) / 819e9)
    value, note = ssmg_scan_roofline.read(traced)
    assert value == pytest.approx(100 * least["seconds"] / 0.012) and 0 < value < 100
    # the update's: the live tokens no scan walked, in 6 layers: 2 x 2 MB and the groups' B and C a row
    rows = (20000 + 64 * 256 - 19900) * 6
    value, note = ssmg_update_roofline.read(traced)
    assert note["row_updates"] == rows and note["kernel_s"] == 0.005
    assert value == pytest.approx(100 * rows * (2 * 2097152 + 4096) / 819e9 / 0.005)
    # the experts': held rows x 4 x hidden x width against the fewest matrices of TWO an expert
    held = run.counters["moe_held_picks"]
    value, note = relu2_expert_ffn_roofline.read(traced)
    assert note["calls"] == 4 and note["grouped_matmul_s"] == pytest.approx(0.096) and note["held_rows"] == held
    assert note["compute_s"] == pytest.approx(held * 4 * 2688 * 1856 / 197e12, rel=1e-4)
    hit = run.counters["moe_experts_hit"]  # two matrices an expert NAMED, however many rows name it
    assert note["matrix_reads"] == 2 * hit and note["memory_s"] == pytest.approx(
        (2 * hit * 2688 * 1856 * 2 + held * 2 * 2688 * 2) / 819e9, rel=1e-4) and 0 < value
    without = serve_run(trace=traced.trace)
    del without.counters["moe_experts_hit"]
    assert relu2_expert_ffn_roofline.read(without) is None
    assert shapes.matrix_bytes(run.sizes) == 2688 * 1856 * 2 and shapes.layers(run.sizes, "E") == 6
    value, note = scmoe_held_row_fill.read(run)  # the metric file relu2.held_row_fill names this reader
    assert value == pytest.approx(100 * held / run.counters["moe_expert_rows"])
    assert common.load_json("metrics", "relu2.held_row_fill.json")["reader"] == "scmoe_held_row_fill"


def test_a_program_without_the_family_gives_nothing_and_does_not_raise():
    """What the parent commit, and every other configuration, hands these readers."""
    there = trace_of((CHUNK[3:], "fwd_n64_t512_b8"))
    mistral = serve_run(counters={"table_slots": 640, "live_blocks": 200}, pool_shapes=[(16, 368, 8, 128, 128)],
                        sizes={"hidden_size": 4096, "num_hidden_layers": 16}, trace=there)
    granite = serve_run(sizes=common.published_sizes(common.load_json(
        "configs", "granite-4.0-h-small-serve-ep2-10l.json"), False),
        pool_shapes=[(1, 1024, 8, 128, 128), (9, 33, 3, 8448), (9, 33, 128, 64, 128)],
        trace=trace_of((CHUNK, "fwd_n32_t512_b20")))
    for run in (mistral, granite, serve_run(counters={}, trace=there)):
        for reader in (ssmg_scan_roofline, ssmg_update_roofline, relu2_expert_ffn_roofline):
            assert reader.read(run) is None, reader.__name__
    assert ssmg_scan_roofline.read(serve_run(trace=there)) is None  # this configuration, no such kernel ran
    assert ssmg_update_roofline.read(serve_run(trace=there)) is None
    for run in (mistral, granite, types.SimpleNamespace(kind="train", trace=None, sizes={})):
        assert ssmg_state_bytes_per_seq.read(run) is None
    assert relu2_expert_ffn_roofline.read(types.SimpleNamespace(kind="serve", sizes={}, trace=None)) is None


@pytest.mark.reads_benchmark
def test_the_borrowed_readers_are_right_for_this_cell_and_the_others_are_not():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n64_t512_b8"), (DECODE, "burst_n64_k16_b8")))
    assert table_fill.read(run)[0] == pytest.approx(100 * 800 / 2560)
    assert gdn_chunk_fill.read(run)[0] == pytest.approx(100 * 6 * 19900 / 192000)  # right here, and ssm.chunk_fill
    # is pinned to its one cell by tests/chipbench/test_readers_ssm.py: left off (PERF.md section 7)
    # left off the cell, each shown wrong or empty here:
    # Granite's readers by its key names (mamba_n_heads, mamba_d_state): nothing
    assert ssm_state_bytes_per_seq.read(run) is None and ssm_scan_roofline.read(run) is None
    assert ssm_update_roofline.read(run) is None
    assert gdn_state_bytes_per_seq.read(run) is None and kda_state_bytes_per_seq.read(run) is None
    assert mla_pool_bytes_per_token.read(run) is None  # no latent width among the keys
    # the paged kernel's roofline takes every one of the 14 layers for an attention layer (two are)
    assert paged_attention_roofline.read(run) is not None
    # the row fill counts the half of the picks that are held elsewhere among its rows
    assert moe_row_fill.read(run)[0] > 150
    assert scmoe_expert_ffn_roofline.read(run) is None  # LongCat's key names
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    every = [w["name"] for w in bench["workloads"]]
    lists = {m["name"]: m.get("workloads", every) for m in bench["per_layer"]}
    for name in ("paged_attention_roofline", "pool.moved_share", "moe.ffn_share", "moe.expert_ffn_roofline",
                 "moe.held_ffn_share", "moe.row_fill", "mla.pool_bytes_per_token", "mla.kernel_share",
                 "scmoe.held_row_fill", "scmoe.ffn_share", "scmoe.expert_ffn_roofline", "zexp.identity_share",
                 "ssm.scan_roofline", "ssm.update_roofline", "ssm.chunk_fill", "ssm.state_move_share",
                 "ssm.state_bytes_per_seq", "ssm.mixer_share", "ssm.scan_share", "gdn.chunk_fill",
                 "kda.update_roofline", "scope.dense_ffn_share", "swa.attention_roofline"):
        assert CELL not in lists[name], name
    for name in ("kv.write_share", "paged.table_fill", "paged.q_fill", "paged.slots_per_step",
                 "step.chunk_ms_per_ktok", "step.busy_ms_per_ktok", "step.burst_ms_per_step", "sched.slot_fill",
                 "sched.tokens_per_fwd", "device.idle_share.serve", "serve.host_syncs_per_tok",
                 "serve.compiles_in_window", "setup.engine_init_s", "setup.trace_s", "setup.lower_s",
                 "setup.load_s", "setup.programs", "scope.attributed_share", "scope.attention_share",
                 "scope.expert_share", "scope.mixer_share", "scope.head_share"):
        assert CELL in lists[name], name
    mine = ("ssmg.update_roofline", "ssmg.scan_roofline", "ssmg.state_bytes_per_seq",
            "relu2.expert_ffn_roofline", "relu2.held_row_fill")
    assert all(lists[name] == [CELL] for name in mine)
    for name in mine:  # the metric file and the benchmark's entry agree, found by name
        spec = common.load_json("metrics", name + ".json")
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert all(spec[k] == entry[k] for k in ("unit", "better", "source", "layer", "moves"))
        assert entry["moves"] == "serve_tok_s"
    ends = {m["name"]: m.get("workloads", every) for m in bench["end_to_end"]}
    assert all(CELL in ends[name] for name in ("serve_tok_s", "ttft_p95_ms", "tpot_p95_ms"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "scmoe-decode-wide", 1)
