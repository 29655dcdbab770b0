"""The readers of the expert FFN's metrics and the counts behind them, on
hand-built inputs: which operations of a traced program are the expert FFN's,
the fewest matrix reads, and that a program without the counters or the
kernels (the parent commit, a dense model) gives nothing and does not raise."""

import types

import pytest

from chipbench.readers import moe_expert_ffn_roofline, moe_ffn_share, moe_row_fill
from chipbench.reduce import moe_shapes, xplane

US = 1_000_000  # ns in the unit of the durations below (a millisecond)
SIZES = {"hidden_size": 2048, "intermediate_size": 1024, "num_hidden_layers": 8,
         "num_experts": 64, "num_experts_per_tok": 8}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def serve_run(**fields):
    fields = {"kind": "serve", "trace": None, "counters": {}, "sizes": SIZES, "peaks": PEAKS,
              "forwards": 2, **fields}
    return types.SimpleNamespace(**fields)


def traced(ops, modules):
    return xplane.Reduction({"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
                             "host": []})


# a chunk program (256 slots x top-8 = 2,048 rows) and a decode program (32 x 8 = 256)
CHUNK = [("%gmm.11 bf16[2048,1024]", 400), ("%gmm.12 bf16[2048,1024]", 400),
         ("%gmm.13 bf16[2048,2048]", 400),
         ("%sort.67 (s32[2048]", 5), ("%sort.66 (f32[256,64]", 3),     # argsort, the router's top-k
         ("%fusion.263 s32[512]", 4), ("%fusion.262 s32[513]", 4), ("%fusion.268 s32[527]", 4),
         ("%fusion.9 f32[256,64]", 2),                                  # the router's softmax
         ("%fusion.277 bf16[2048,2048]", 20), ("%multiply_multiply_fusion.2 bf16[2048,1024]", 6),
         ("%fusion.278 s32[2048]", 3), ("%compare_select_fusion.66 s32[2048,1]", 1),
         # not the expert FFN's: dense per-token layers, attention, the pool, the picks
         ("%fusion.270 bf16[256,2048]", 50), ("%constant_dynamic-slice_fusion.7 bf16[1,2048,2048]", 30),
         ("%paged_attention.11 bf16[32,16,256,128]", 900), ("%copy.111 bf16[8,368,16,128,128]", 700),
         ("%copy.132 s32[256,8]", 1), ("%fusion.150 bf16[256,50304]", 40),
         ("%while.3 (s32[]", 5000)]                                     # a container: its children count
DECODE = [("%gmm.13 bf16[256,1024]", 350), ("%gmm.14 bf16[256,1024]", 350),
          ("%gmm.15 bf16[256,2048]", 350), ("%sort.77 (s32[256]", 2), ("%fusion.262 s32[513]", 3),
          ("%fusion.272 bf16[256,2048]", 4),                            # 256 rows gathered: dispatch HERE
          ("%maximum_bitcast_fusion.5 bf16[256,128]", 9),               # 256 rows of something else
          ("%paged_attention.13 bf16[32,16,8,128]", 1100), ("%fusion.1 bf16[32,1,2048]", 10)]
DENSE = [("%fusion.270 bf16[256,2048]", 50), ("%sort.5 (f32[32,50304]", 10)]  # a program with no gmm


def program(start_us, ops, name):
    t, events = start_us * US, []
    for op, dur_us in ops:
        if op.startswith("%while"):
            events.append((op, start_us * US, dur_us * US))
            continue
        events.append((op, t, dur_us * US))
        t += dur_us * US
    return events, (f"jit_{name}(1)", start_us * US, 6000 * US)


def trace_of(*programs):
    ops, modules = [], []
    for i, (body, name) in enumerate(programs):
        events, module = program(10_000 * i, body, name)
        ops += events
        modules.append(module)
    return traced(ops, modules)


def test_the_expert_ffns_operations_are_found_by_kind_program_by_program():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n32_t256_b12"), (DECODE, "burst_n32_k16"),
                                   (DENSE, "pick_n32")),
                    counters={"moe_routed_rows": 2304, "moe_expert_rows": 2304})
    kinds = {}
    for prog, name, ns, kind in moe_ffn_share.operations(run):
        kinds.setdefault(kind, set()).add((prog.split("(")[0], name.split(" ")[0]))
    assert kinds["grouped_matmul"] == {("jit_fwd_n32_t256_b12", f"%gmm.{i}") for i in (11, 12, 13)} | {
        ("jit_burst_n32_k16", f"%gmm.{i}") for i in (13, 14, 15)}
    assert {n for _, n in kinds["sort"]} == {"%sort.67", "%sort.66", "%sort.77"}  # not pick's sort.5
    assert {n for _, n in kinds["group_metadata"]} == {"%fusion.263", "%fusion.262", "%fusion.268"}
    assert kinds["router"] == {("jit_fwd_n32_t256_b12", "%fusion.9")}
    # [256, 2048] is the decode program's gathered rows and the chunk program's dense layers
    assert kinds["dispatch"] == {("jit_fwd_n32_t256_b12", n) for n in (
        "%fusion.277", "%multiply_multiply_fusion.2", "%fusion.278", "%compare_select_fusion.66")} | {
        ("jit_burst_n32_k16", "%fusion.272")}
    value, note = moe_ffn_share.read(run)
    ffn_us = 3 * 400 + 8 + 12 + 2 + 30 + 3 * 350 + 2 + 3 + 4
    assert note["ffn_s"] == pytest.approx(ffn_us / 1e3, abs=1e-4)
    assert note["grouped_matmul_s"] == pytest.approx(2.25) and note["sort_s"] == pytest.approx(0.01)
    assert value == pytest.approx(100 * ffn_us * US / 1e9 / run.trace.busy_s, rel=1e-3)


def test_fewest_matrix_reads_fills_the_widest_calls_first():
    reads = moe_shapes.fewest_matrix_reads
    assert reads([2048, 256, 256, 16], 2048 + 256, 64) == 128      # two calls in use, 64 each
    assert reads([2048, 256, 256, 16], 2048 + 256 + 10, 64) == 138  # ten rows more: ten matrices
    assert reads([16, 16], 24, 64) == 24 and reads([16, 16], 0, 64) == 0
    assert reads([256] * 4, 4 * 256, 64) == 4 * 64                  # full calls: every expert, once
    # any other laying of the same rows reads at least as much
    assert reads([2048, 256], 300, 64) == 64 <= min(64, 150) + min(64, 150)


def test_roofline_is_the_floor_over_the_kernels_time_and_cannot_pass_100_when_they_run_at_it():
    # a decode program alone: three calls of 256 rows, all routed, each reading 64 matrices
    rows = 256
    floor_s = (3 * 64 * 2048 * 1024 * 2 + rows * 2 * 2048 * 2) / 819e9
    at_roofline = int(floor_s / 3 * 1e9) + 1
    ops = [(f"%gmm.{i} bf16[256,{w}]", at_roofline / US) for i, w in ((13, 1024), (14, 1024), (15, 2048))]
    run = serve_run(trace=trace_of((ops, "fwd_n32_t1_b12")), counters={"moe_routed_rows": rows})
    value, note = moe_expert_ffn_roofline.read(run)
    assert 99.9 < value <= 100.0 and note["mostly"] == "memory_s" and note["calls"] == 3
    assert note["matrix_reads"] == 192
    # half the rows live: the floor falls (fewer matrices can be in use), the time does not
    half = serve_run(trace=run.trace, counters={"moe_routed_rows": 16})
    assert moe_expert_ffn_roofline.read(half)[0] == pytest.approx(
        100 * (48 * 2048 * 1024 * 2 + 16 * 2 * 2048 * 2) / 819e9 / (3 * at_roofline / 1e9), rel=1e-3)


def test_row_fill_is_routed_over_computed():
    value, note = moe_row_fill.read(serve_run(counters={"moe_routed_rows": 1028160,
                                                        "moe_expert_rows": 1054080}))
    assert value == pytest.approx(97.541, abs=1e-3)
    assert note == {"moe_routed_rows": 1028160, "moe_expert_rows": 1054080}


@pytest.mark.parametrize("reader", [moe_row_fill, moe_ffn_share, moe_expert_ffn_roofline])
def test_a_program_without_the_counters_or_the_kernels_gives_nothing(reader):
    dense_trace = trace_of((DENSE, "fwd_n32_t256_b12"))
    for run in (serve_run(), serve_run(counters={"moe_routed_rows": 0, "moe_expert_rows": 0}),
                serve_run(trace=dense_trace),                       # the parent: no counter, no kernel
                serve_run(trace=dense_trace, counters={"moe_routed_rows": 0, "moe_expert_rows": 0}),
                serve_run(trace=dense_trace, sizes={"hidden_size": 4096}),   # a dense configuration
                serve_run(kind="train", counters={"moe_routed_rows": 5, "moe_expert_rows": 9})):
        assert reader.read(run) is None
