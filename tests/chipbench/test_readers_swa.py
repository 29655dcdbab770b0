"""The cell ``serve.swa-mixed-lengths``: its four readers on hand-built traces,
scope tables and counters, what a program without the family hands them (the
parent commit: nothing, and no raise), the readers the cell borrows, and the
readers that would read and read wrong, which are left off it."""

import json
import os
import types

import pytest

from chipbench import common
from chipbench.generators.waves import Traffic
from chipbench.readers import (kv_behind_window_share, moe_row_fill, paged_attention_roofline,
                               swa_attention_roofline, swa_full_kernel_share, swa_kernel_share,
                               table_fill)
from chipbench.reduce import shapes, swa_shapes, xplane
from tests.chipbench.conftest import ROOT

CONFIG, CELL = "trinity-large-serve-ep8-8l", "serve.swa-mixed-lengths"
SPEC = common.load_json("configs", CONFIG + ".json")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000  # ns


def serve_run(**fields):
    wave = Traffic(common.load_json("traffic", "mixed-lengths.json")["params"], 1, SPEC["vocab_size"])
    fields = {"kind": "serve", "trace": None, "sizes": common.published_sizes(SPEC, False),
              "peaks": PEAKS, "lengths": wave.lengths, "max_new_tokens": wave.max_new_tokens,
              "counters": {"kv_blocks_behind_window": 6 * 500, "live_blocks": 800, "table_slots": 2560,
                           "moe_routed_rows": 90_000 * 8, "moe_expert_rows": 1280 * 2 * 44},
              **fields}
    return types.SimpleNamespace(**fields)


def traced(kernel_ms=(300, 260), busy_extra_ms=1000):
    """A wave whose windowed layers' kernels took ``kernel_ms[0]`` and whose full layers'
    ``kernel_ms[1]``, with the scope table the program would give for them."""
    ops = [("%paged_attention.1 = bf16[8,12288,128]{2,1,0} custom-call(...)", 0, kernel_ms[0] * MS),
           ("%paged_attention.2 = bf16[8,12288,128]{2,1,0} custom-call(...)", 400 * MS, kernel_ms[1] * MS),
           ("%fusion.3 = bf16[1,2048,3072]{2,1,0} fusion(...)", 800 * MS, busy_extra_ms * MS)]
    ops = [(xplane.short_name(name), start, dur) for name, start, dur in ops]
    trace = xplane.Reduction({"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [("jit_fwd_n1_t2048_b32(1)", 0, 2000 * MS)]}}, "host": []})
    split = {"paths": {("attn_kernel", "attn_window"): (kernel_ms[0] + 20) * MS,
                       ("attn_kernel", "attn_full"): (kernel_ms[1] + 8) * MS,
                       ("attn_qkv", "attn_window"): 90 * MS, ("layer_finish", ): 500 * MS},
             "kernels": {("attn_window", "paged_attention"): kernel_ms[0] * MS,
                         ("attn_full", "paged_attention"): kernel_ms[1] * MS}}
    return serve_run(trace=trace, scope_split={"devices": {"/device:TPU:0": split}})


def test_the_wave_is_the_issues_and_its_attention_is_counted_by_layer_kind():
    run = serve_run()
    assert sorted(run.lengths) == [513, 1030, 1546, 2121, 2794, 3613, 4644, 6004, 7911, 10856, 16288,
                                   32721] and sum(run.lengths) == 90041
    assert swa_shapes.is_family(run.sizes) and swa_shapes.layer_windows(run.sizes) == [4096] * 3 + [
        None] + [4096] * 3 + [None]
    kinds = swa_shapes.by_kind(run.sizes)
    assert (kinds["window"]["num_hidden_layers"], kinds["full"]["num_hidden_layers"]) == (6, 2)
    # a full layer's causal pairs are 2.8 times a windowed layer's on this wave: what a walk that
    # does not skip pays in every windowed layer
    pairs = lambda window: sum(shapes._keys_seen_sum(0, n, window) for n in run.lengths)
    assert pairs(None) / pairs(4096) == pytest.approx(2.808, abs=1e-3)
    least = swa_shapes.attention_least_seconds(run.sizes, run.lengths, 32, PEAKS)
    per_pair = 4 * 128 * 48
    assert least["prefill_compute_s"] == pytest.approx(
        per_pair * (6 * pairs(4096) + 2 * pairs(None)) / 197e12, rel=1e-3)
    assert least["seconds"] == pytest.approx(least["window_layers_s"] + least["full_layers_s"])
    # the accepted reader counts ONE window for all eight layers: a fifth less than the work
    # (the full layers taken for windowed ones): left off this cell
    one_window = shapes.paged_attention_least_seconds(run.sizes, run.lengths, 32, PEAKS)["seconds"]
    assert one_window < 0.72 * least["seconds"]


def test_the_swa_readers_count_what_is_certain():
    run = traced()
    value, note = swa_attention_roofline.read(run)
    least = swa_shapes.attention_least_seconds(run.sizes, run.lengths, 32, PEAKS)["seconds"]
    assert value == pytest.approx(100 * least / 0.56) and 0 < value < 100
    assert note["kernel_s"] == 0.56 and note["mostly"] == "prefill_compute_s"
    assert paged_attention_roofline.read(run)[0] < 0.72 * value  # it would read, and read low
    value, note = swa_kernel_share.read(run)
    assert value == pytest.approx(100 * 0.32 / run.trace.busy_s) and note["paged_attention_s"] == 0.3
    value, note = swa_full_kernel_share.read(run)
    assert value == pytest.approx(100 * 0.268 / run.trace.busy_s) and note["scope_s"] == 0.268
    value, note = kv_behind_window_share.read(run)
    assert value == pytest.approx(100 * 3000 / (800 * 8)) and note["window_layer_share"] == 0.75
    # a walk that multiplied the blocks behind the windows: the same least time over more kernel time
    slow = traced(kernel_ms=(840, 260))
    assert swa_attention_roofline.read(slow)[0] == pytest.approx(100 * least / 1.1)


def test_a_program_without_the_family_gives_nothing_and_does_not_raise():
    """What the parent commit, and every other configuration, hands these readers:
    no such counters, no such scopes, no ``layer_types`` of these kinds."""
    mistral = common.published_sizes(common.load_json("configs", "mistral-7b-serve-16l.json"), False)
    granite = common.published_sizes(common.load_json(
        "configs", "granite-4.0-h-small-serve-ep2-10l.json"), False)
    there = traced()
    parent_split = {"devices": {"/device:TPU:0": {"paths": {("attn_kernel", ): 500 * MS},
                                                  "kernels": {("attn_kernel", "paged_attention"): 500 * MS}}}}
    for run in (serve_run(sizes=mistral, counters={"live_blocks": 5, "table_slots": 9}, trace=there.trace,
                          scope_split=parent_split),
                serve_run(sizes=granite, counters={}, trace=there.trace, scope_split=parent_split),
                serve_run(counters={}), types.SimpleNamespace(kind="train", trace=None, sizes={})):
        for reader in (swa_attention_roofline, swa_kernel_share, swa_full_kernel_share,
                       kv_behind_window_share):
            assert reader.read(run) is None, (reader.__name__, run)
    # the parent's program on THIS configuration: the kernel's events are there, the scopes are not
    parent = serve_run(counters={"live_blocks": 5}, trace=there.trace, scope_split=parent_split)
    assert swa_kernel_share.read(parent) is None and swa_full_kernel_share.read(parent) is None
    assert kv_behind_window_share.read(parent) is None


@pytest.mark.reads_benchmark
def test_the_borrowed_readers_are_right_for_this_cell_and_the_others_are_not():
    run = traced()
    assert table_fill.read(run)[0] == pytest.approx(100 * 800 / 2560)
    # the row fill counts the seven eighths of the picks that are held elsewhere among its rows
    assert moe_row_fill.read(run)[0] > 500
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    every = [w["name"] for w in bench["workloads"]]  # an entry without a list is read in every cell
    lists = {m["name"]: m.get("workloads", every) for m in bench["per_layer"]}
    for name in ("paged_attention_roofline", "pool.moved_share", "moe.ffn_share", "moe.expert_ffn_roofline",
                 "moe.held_ffn_share", "moe.row_fill", "mla.attention_roofline", "scope.mixer_share",
                 "step.burst_ms_per_step", "ssm.state_move_share", "dsa.attention_roofline"):
        assert CELL not in lists[name], name
    for name in ("kv.write_share", "paged.table_fill", "paged.q_fill", "paged.slots_per_step",
                 "step.chunk_ms_per_ktok", "step.busy_ms_per_ktok", "sched.slot_fill",
                 "sched.tokens_per_fwd", "device.idle_share.serve", "serve.host_syncs_per_tok",
                 "serve.compiles_in_window", "setup.engine_init_s", "setup.trace_s", "setup.lower_s",
                 "setup.load_s", "setup.programs", "scope.attributed_share", "scope.attention_share",
                 "scope.expert_share", "scope.dense_ffn_share", "scope.head_share"):
        assert CELL in lists[name], name
    mine = ("swa.attention_roofline", "swa.window_kernel_share", "swa.full_kernel_share",
            "kv.behind_window_share")
    assert all(lists[name] == [CELL] for name in mine)
    moved = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert all(moved[name] == "serve_tok_s" for name in mine)
    for name in mine:  # the metric file and the benchmark's entry agree, found by name
        spec = common.load_json("metrics", name + ".json")
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert all(spec[k] == entry[k] for k in ("unit", "better", "source", "layer", "moves"))
    ends = {m["name"]: m.get("workloads", every) for m in bench["end_to_end"]}
    assert CELL in ends["serve_tok_s"] and CELL in ends["ttft_p95_ms"] and CELL not in ends["tpot_p95_ms"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "mixed-lengths", 1)
