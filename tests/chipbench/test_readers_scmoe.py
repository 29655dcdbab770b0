"""The cell ``serve.scmoe-decode-wide``: its four new readers on hand-built
traces and counters (the roofline at its extremes), the readers it borrows, and
the readers that would read and read wrong, which are left off it."""

import json
import os
import types

import pytest

from chipbench import common
from chipbench.readers import (burst_ms_per_step, chunk_ms_per_ktok, gdn_scan_share, kv_write_share,
                               mla_attention_roofline, mla_kernel_share, mla_pool_bytes_per_token,
                               moe_expert_ffn_roofline, moe_ffn_share, moe_held_ffn_share,
                               moe_row_fill, paged_attention_roofline, q_fill,
                               scmoe_expert_ffn_roofline, scmoe_ffn_share, scmoe_held_row_fill,
                               table_fill, zexp_identity_share)
from chipbench.reduce import moe_shapes, scmoe_shapes, xplane
from tests.chipbench.conftest import ROOT

CONFIG, CELL = "longcat-flash-omni-serve-ep32-4l", "serve.scmoe-decode-wide"
POOL = [(2, ), (8, 1024, 1, 128, 640)]  # the tallies beside the one latent leaf
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
US = 1_000_000  # ns in the unit of the durations below (a millisecond)
STEP = [("%fusion.1 = f32[64,768]{1,0} fusion(...)", 2),                      # the router's logits
        ("%fusion.2 = f32[64,768]{1,0} fusion(...)", 1),                      # softmax, + bias
        ("%sort.3 = (f32[64,768]{1,0}, s32[64,768]{1,0}) sort(...)", 4),      # the top-k
        ("%sort.4 = (s32[768]{0}, s32[768]{0}) sort(...)", 3),                # rows by group
        ("%fusion.5 = s32[64]{0} fusion(...)", 1),                            # group sizes: 4 x 16
        ("%fusion.6 = s32[69]{0} fusion(...)", 1),                            # the tile schedule
        ("%fusion.7 = bf16[768,6144]{1,0} fusion(...)", 6),                   # the row gather
        ("%gmm.8 = bf16[768,2048]{1,0} custom-call(...)", 20),
        ("%gmm.9 = bf16[768,2048]{1,0} custom-call(...)", 20),
        ("%fusion.10 = bf16[768,2048]{1,0} fusion(...)", 2),                  # silu(gate) * up
        ("%gmm.11 = bf16[768,6144]{1,0} custom-call(...)", 20),
        ("%fusion.12 = f32[64,6144]{1,0} fusion(...)", 5),     # combine + identity add: not told apart
        ("%fusion.13 = bf16[1,64,12288]{2,1,0} fusion(...)", 60),             # a dense FFN: not ours
        ("%fusion.16 = (f32[64]{0}, bf16[64,1,6144]{2,1,0}) fusion(...)", 30),  # W_o with a norm's sums:
        # named by its first output, as long as the 4 x 16 groups, and not ours (0.32 s of a chip wave)
        ("%kv_write.14 = bf16[8192,1,128,640]{3,2,1,0} custom-call(...)", 1),
        ("%paged_attention.15 = bf16[1,4096,512]{2,1,0} custom-call(...)", 10)]


def trace_of(*programs):
    ops, modules = [], []
    for i, (body, name) in enumerate(programs):
        t = 10_000 * US * i
        modules.append((f"jit_{name}(1)", t, 6000 * US))
        for op, us in body:
            ops.append((xplane.short_name(op), t, us * US))
            t += us * US
    return xplane.Reduction({"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}}, "host": []})


def sizes_of():
    spec = common.load_json("configs", CONFIG + ".json")
    sizes = common.published_sizes(spec, False)
    sizes["num_hidden_layers"] = 2 * sizes["num_layers"]  # what entries/serve_sublayers.py adds
    return sizes


def serve_run(**fields):
    fields = {"kind": "serve", "trace": None, "sizes": sizes_of(), "peaks": PEAKS,
              "lengths": [131, 509], "max_new_tokens": 256, "prompt_tokens": 640, "forwards": 300,
              "stepwise_forwards": 44,
              "counters": {"moe_identity_picks": 600_000, "moe_held_picks": 36_000,
                           "moe_routed_rows": 1_769_472, "moe_expert_rows": 1_800_000,
                           "table_slots": 1024, "live_blocks": 400, "live_tokens": 36_864,
                           "attn_token_slots": 37_000, "token_slots": 37_000},
              "pool_shapes": POOL, **fields}
    return types.SimpleNamespace(**fields)


def test_the_counter_readers_read_the_devices_own_tallies():
    run = serve_run()
    value, note = zexp_identity_share.read(run)
    assert value == pytest.approx(100 * 600_000 / 1_769_472)
    assert note["held_elsewhere"] == 1_769_472 - 636_000 and note["picks"] == 36_864 * 12 * 4
    value, note = scmoe_held_row_fill.read(run)
    assert value == pytest.approx(2.0) and note["moe_expert_rows"] == 1_800_000
    # what moe.row_fill would read here: every pick a row, 98% full of rows that multiply nothing
    assert moe_row_fill.read(run)[0] == pytest.approx(100 * 1_769_472 / 1_800_000)


def test_the_share_finds_the_expert_layers_operations_under_this_configurations_keys():
    sizes = sizes_of()
    assert scmoe_shapes.groups(sizes) == 64 and 768 in scmoe_shapes.router_widths(sizes)
    assert scmoe_shapes.picks(sizes, 36_864) == 1_769_472
    run = serve_run(trace=trace_of((STEP, "burst_n64_k200"), (STEP[-4:], "fwd_n64_t1_b8")))
    kinds = {name.split(" ")[0]: kind for _, name, _, kind in scmoe_ffn_share.operations(run)}
    assert kinds == {"%fusion.1": "router", "%fusion.2": "router", "%sort.3": "sort",
                     "%sort.4": "sort", "%fusion.5": "group_metadata", "%fusion.6": "group_metadata",
                     "%fusion.7": "dispatch", "%gmm.8": "grouped_matmul", "%gmm.9": "grouped_matmul",
                     "%fusion.10": "dispatch", "%gmm.11": "grouped_matmul"}
    value, note = scmoe_ffn_share.read(run)
    assert note["grouped_matmul_s"] == pytest.approx(60e-3) and note["router_s"] == pytest.approx(3e-3)
    assert note["sort_s"] == pytest.approx(7e-3) and note["dispatch_s"] == pytest.approx(8e-3)
    assert value == pytest.approx(100 * 80e-3 / run.trace.busy_s)


def test_the_roofline_counts_the_true_held_rows_and_the_fewest_matrix_reads():
    sizes = sizes_of()
    assert scmoe_shapes.matrix_bytes(sizes) == 6144 * 2048 * 2
    assert scmoe_shapes.expert_ffn_flops(sizes, 1000) == 1000 * 6 * 6144 * 2048
    least = scmoe_shapes.expert_ffn_least_seconds(sizes, 1000, [768] * 12, PEAKS)
    # twelve calls are four FFNs: 1,000 rows laid into two of them (768 + 232), 16 matrices each,
    # and each FFN's gate, up and down calls read their own
    assert least["matrix_reads"] == 96 == 3 * moe_shapes.fewest_matrix_reads([768] * 4, 1000, 16)
    assert least["memory_s"] == pytest.approx((96 * 6144 * 2048 * 2 + 1000 * 2 * 6144 * 2) / 819e9)
    assert least["compute_s"] == pytest.approx(1000 * 6 * 6144 * 2048 / 197e12)
    run = serve_run(trace=trace_of((STEP, "burst_n64_k200")),
                    counters={"moe_held_picks": 16, "moe_expert_rows": 768})
    value, note = scmoe_expert_ffn_roofline.read(run)
    # 16 held rows in one FFN's three calls of 768: 16 + 16 + 16 matrices at least
    assert note["matrix_reads"] == 48 and note["calls"] == 3 and note["mostly"] == "memory_s"
    assert value == pytest.approx(100 * note["seconds"] / 60e-3, rel=1e-3)


@pytest.mark.parametrize("rows_a_call,held_a_call", [(768, 16), (768, 768), (12288, 256), (16, 1)])
def test_the_roofline_reads_under_100_at_its_extremes(rows_a_call, held_a_call):
    """A kernel at the chip's peaks that reads each matrix a held row needs once
    and multiplies the held rows alone takes at least the counted time: with
    every call holding ``held_a_call`` rows, the calls' own least time (each
    reads ``min(16, held)`` matrices and its rows in and out) is never under
    the reader's floor over the same calls."""
    sizes, calls = sizes_of(), 300
    floor = scmoe_shapes.expert_ffn_least_seconds(sizes, calls // 3 * held_a_call,
                                                  [rows_a_call] * calls, PEAKS)
    a_call = max(held_a_call * 2 * 6144 * 2048 / 197e12,
                 (min(16, held_a_call) * 6144 * 2048 * 2 + held_a_call * 2 * 6144 * 2 / 3) / 819e9)
    assert 0 < floor["seconds"] <= calls * a_call * (1 + 1e-9)


def test_a_program_without_the_family_gives_nothing_and_does_not_raise():
    """What the parent commit, and every other configuration, hands these
    readers: no tallies among the counters, no identity experts among the sizes."""
    traced = trace_of((STEP, "burst_n64_k200"))
    parent = serve_run(counters={"moe_routed_rows": 100, "moe_expert_rows": 128, "live_tokens": 9},
                       trace=traced)  # this configuration on a program that tallies nothing
    olmoe = serve_run(sizes=common.published_sizes(common.load_json(
        "configs", "olmoe-1b-7b-serve-8l.json"), False), trace=traced,
        counters={"moe_routed_rows": 100, "moe_expert_rows": 128})
    for reader in (zexp_identity_share, scmoe_held_row_fill, scmoe_expert_ffn_roofline):
        assert reader.read(parent) is None and reader.read(olmoe) is None
        assert reader.read(types.SimpleNamespace(kind="serve")) is None  # no counters at all
        assert reader.read(types.SimpleNamespace(kind="train", counters={})) is None
    assert scmoe_ffn_share.read(olmoe) is None and scmoe_ffn_share.read(serve_run()) is None
    assert scmoe_ffn_share.read(parent) is not None  # the trace alone: the parent has no such trace


@pytest.mark.reads_benchmark
def test_the_borrowed_readers_are_right_for_this_cell_and_the_others_are_not():
    run = serve_run(trace=trace_of((STEP, "fwd_n64_t512_b8"), (STEP, "burst_n64_k200")))
    assert kv_write_share.read(run)[1]["calls"] == 2
    assert chunk_ms_per_ktok.read(run)[1]["chunk_programs_run"] == 1
    assert burst_ms_per_step.read(run)[1] == {"burst_programs_run": 1, "steps": 256}
    assert table_fill.read(run)[0] == pytest.approx(100 * 400 / 1024) and q_fill.read(run)[0] > 99
    # one latent leaf of 640 lanes; the tallies' (2,) is no pool leaf
    assert mla_pool_bytes_per_token.read(run)[0] == 1280.0
    # eight attention sublayers (num_hidden_layers, which the entry derives) of 64 heads
    value, note = mla_attention_roofline.read(run)
    assert 0 < value < 100 and note["kernel_s"] == pytest.approx(20e-3)
    assert mla_kernel_share.read(run)[0] == pytest.approx(100 * 20e-3 / run.trace.busy_s)
    without = serve_run(trace=run.trace, sizes={k: v for k, v in run.sizes.items()
                                                if k != "num_hidden_layers"})
    with pytest.raises(KeyError, match="num_hidden_layers"):  # why the entry exists
        mla_attention_roofline.read(without)
    # left off the cell: each reads nothing here, or reads wrong
    with pytest.raises(KeyError):  # asks for DeepSeek-V2's first_k_dense_replace, moe_intermediate_size
        moe_held_ffn_share.operations(run)
    assert moe_held_ffn_share.read(run) is None and moe_ffn_share.read(run) is None
    assert gdn_scan_share.read(run) is None
    with pytest.raises(KeyError):  # shapes.py counts a dense decoder's K and V heads
        paged_attention_roofline.read(run)
    with pytest.raises(KeyError):  # intermediate_size and num_experts: another family's keys
        moe_expert_ffn_roofline.read(run)
    assert moe_row_fill.read(run)[0] > 98  # reads, and reads the slots' fill for the matmuls'
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    every = [w["name"] for w in bench["workloads"]]
    lists = {m["name"]: m.get("workloads", every) for m in bench["per_layer"]}
    for name in ("paged_attention_roofline", "pool.moved_share", "moe.ffn_share",
                 "moe.expert_ffn_roofline", "moe.row_fill", "moe.held_ffn_share",
                 "conv.mixer_share", "conv.state_move_share", "conv.state_bytes_per_seq",
                 "gdn.mixer_share", "gdn.scan_roofline", "gdn.scan_share", "gdn.chunk_fill",
                 "gdn.state_bytes_per_seq", "dsa.selected_share", "dsa.attended_per_selected",
                 "dsa.indexer_share", "dsa.attention_roofline", "dsa.index_roofline"):
        assert CELL not in lists[name], name
    for name in ("kv.write_share", "paged.table_fill", "paged.q_fill", "paged.slots_per_step",
                 "step.chunk_ms_per_ktok", "step.busy_ms_per_ktok", "step.burst_ms_per_step",
                 "sched.slot_fill", "sched.tokens_per_fwd", "device.idle_share.serve",
                 "serve.host_syncs_per_tok", "serve.compiles_in_window", "setup.engine_init_s",
                 "setup.trace_s", "setup.lower_s", "setup.load_s", "setup.programs",
                 "mla.pool_bytes_per_token", "mla.attention_roofline", "mla.kernel_share",
                 "zexp.identity_share", "scmoe.held_row_fill", "scmoe.ffn_share",
                 "scmoe.expert_ffn_roofline"):
        assert CELL in lists[name], name
    for name in ("zexp.identity_share", "scmoe.held_row_fill", "scmoe.ffn_share",
                 "scmoe.expert_ffn_roofline"):
        assert lists[name] == [CELL], name
    ends = {m["name"]: m.get("workloads", every) for m in bench["end_to_end"]}
    assert all(CELL in ends[name] for name in ("serve_tok_s", "ttft_p95_ms", "tpot_p95_ms"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "scmoe-decode-wide", 1)
    assert "1/32" in cell["why"] and "4 layers" in cell["why"]
