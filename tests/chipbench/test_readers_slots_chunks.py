"""The readers of the metrics that read the program's slot counters and its
bucket-named programs, on hand-built inputs."""

import types

import pytest

from chipbench.readers import chunk_ms_per_ktok, pool_moved_share, slot_fill, table_fill
from chipbench.reduce import xplane

MS = 1_000_000  # ns


def serve_run(**fields):
    fields = {"kind": "serve", "trace": None, "counters": {}, "prompt_tokens": 2000, **fields}
    return types.SimpleNamespace(**fields)


def traced(modules):
    ops = [("fusion.1", 0, 10)]
    return xplane.Reduction({"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
                             "host": []})


# ------------------------------------------------------------------ the fills
@pytest.mark.parametrize("reader,live,slots", [(slot_fill, "live_tokens", "token_slots"),
                                               (table_fill, "live_blocks", "table_slots")])
def test_a_fill_is_live_over_slots_with_both_counts_noted(reader, live, slots):
    value, note = reader.read(serve_run(counters={live: 256, slots: 8192, "host_syncs": 3}))
    assert value == pytest.approx(3.125)
    assert note == {live: 256, slots: 8192}


@pytest.mark.parametrize("reader,live,slots", [(slot_fill, "live_tokens", "token_slots"),
                                               (table_fill, "live_blocks", "table_slots")])
@pytest.mark.parametrize("counters", [{}, {"host_syncs": 3}, "zero"])
def test_a_fill_with_no_denominator_is_nothing_to_read(reader, live, slots, counters):
    # a program that has no such counter (the parent commit), and a window
    # that launched no forward: no division, no zero, nothing
    if counters == "zero":
        counters = {live: 0, slots: 0}
    assert reader.read(serve_run(counters=counters)) is None
    assert reader.read(serve_run(kind="train", counters={live: 1, slots: 2})) is None


# ------------------------------------------------------------------ the chunks
MODULES = [("jit_fwd_n32_t256_b20(123)", 0, 300 * MS),
           ("jit_fwd_n32_t256_b20(123)", 400 * MS, 100 * MS),
           ("jit_fwd_n4_t8_b12(77)", 600 * MS, 50 * MS),
           ("jit_fwd_n32_t1_b20(9)", 700 * MS, 70 * MS),        # one token a row: decode
           ("jit_burst_n16_k64(5)", 800 * MS, 900 * MS),        # a burst
           ("jit_pick_n32(6)", 1800 * MS, 1 * MS),              # a pick
           ("jit__scatter_impl(8)", 1900 * MS, 1 * MS)]


def test_chunk_time_counts_only_forward_programs_with_more_than_one_token_a_row():
    value, note = chunk_ms_per_ktok.read(serve_run(trace=traced(MODULES)))
    assert value == pytest.approx(450.0 / 2.0)  # 450 ms for 2,000 prompt tokens
    assert note["chunk_programs_run"] == 3 and note["chunk_s"] == 0.45
    assert note["top"] == "fwd_n32_t256_b20:2x:0.4000s,fwd_n4_t8_b12:1x:0.0500s"


def test_chunk_time_lists_the_five_buckets_with_most_device_time():
    modules = [(f"jit_fwd_n4_t{2 ** i}_b4(1)", i * 100 * MS, i * MS) for i in range(1, 8)]
    _, note = chunk_ms_per_ktok.read(serve_run(trace=traced(modules)))
    assert [part.split(":")[0] for part in note["top"].split(",")] == [
        f"fwd_n4_t{2 ** i}_b4" for i in (7, 6, 5, 4, 3)]


@pytest.mark.parametrize("run", [
    serve_run(),                                                      # the CPU rehearsal
    serve_run(trace=traced([("jit_fwd(1)", 0, MS), ("jit_burst(2)", MS, MS)])),  # older names
    serve_run(trace=traced(MODULES[3:])),                             # no chunk ran
    serve_run(trace=traced(MODULES), prompt_tokens=0),
    serve_run(trace=traced(MODULES), kind="train")])
def test_chunk_time_with_nothing_to_read(run):
    assert chunk_ms_per_ktok.read(run) is None


# ------------------------------------------------------------------ the pool
POOL = [(16, 368, 8, 128, 128)]  # k and v of mistral-7b-serve-16l: [L, NB, KV, bs, Dh]
# breakdown.device_ops of serve.decode-heavy (ledger, PR 25, the change's side)
DECODE_HEAVY = [("_paged_attention.10_bf16_16_32_8_128_", 5.899543914),
                ("_dynamic-slice_bitcast_fusion.5_bf16_368_8_128_128_", 1.189521581),
                ("_bitcast_dynamic-update-slice_fusion.5_bf16_16_368_8_128_128_", 1.16644976),
                ("_copy.83_bf16_16_368_8_128_128_", 1.042339777),
                ("_copy.82_bf16_16_368_8_128_128_", 1.042311516),
                ("_bitcast_add_fusion.3_bf16_16_1_4096_", 0.579500406),
                ("_fusion.140_bf16_16_14336_", 0.579196364),
                ("_fusion.141_bf16_16_14336_", 0.578946567),
                ("_bitcast_dynamic-update-slice_fusion.4_bf16_16_368_8_128_128_", 0.540790001),
                ("_dynamic-slice_bitcast_fusion.4_bf16_368_8_128_128_", 0.520608992)]


def one_after_another(named_seconds):
    ops, at = [], 0
    for name, seconds in named_seconds:
        ops.append((name, at, round(seconds * 1e9)))
        at += round(seconds * 1e9)
    return xplane.Reduction({"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
                             "host": []})


def test_pool_moved_share_finds_the_six_pool_shaped_operations_of_the_ledger():
    endings = pool_moved_share.pool_endings(POOL)
    assert endings == ("_368_8_128_128_", "_376832_128_")
    found = [n for n, _ in DECODE_HEAVY if pool_moved_share.pool_shaped(n, endings)]
    assert len(found) == 6 and not any("paged_attention" in n or "14336" in n for n in found)
    trace = one_after_another(DECODE_HEAVY)
    value, note = pool_moved_share.read(serve_run(trace=trace, pool_shapes=POOL))
    assert note["moved_s"] == pytest.approx(5.5020, abs=1e-4) and note["operations"] == 6
    assert value == pytest.approx(100 * 5.502021627 / 13.139208878)
    assert (note["dynamic-update-slice_s"], note["dynamic-slice_s"], note["copy_s"],
            note["other_s"]) == (1.7072, 1.7101, 2.0847, 0.0)


def test_pool_moved_share_reads_the_traces_own_spelling_and_self_time():
    # as the trace prints them; a pool update inside a loop counts once, the
    # loop itself not; the kernel's output and a flattened pool are told apart
    ops = [("%while.3 (s32[]", 0, 1000),
           ("%fusion.5 bf16[16,368,8,128,128]", 100, 300),
           ("%constant_dynamic-slice_fusion.16 bf16[1,368,8,128,128]", 400, 100),
           ("%fusion.9 bf16[376832,128]", 500, 50),
           ("%paged_attention.4 bf16[4,32,256,128]", 550, 250),
           ("%custom-call.2 | paged_attention bf16[16,368,8,128,128]", 800, 100),
           ("%fusion.7 bf16[256,14336]", 900, 100)]
    trace = xplane.Reduction({"devices": {"d": {"ops": ops, "modules": []}}, "host": []})
    value, note = pool_moved_share.read(serve_run(trace=trace, pool_shapes=POOL))
    assert value == pytest.approx(100 * 450 / 1000) and note["operations"] == 3
    # nothing moved the pool: a reading of nothing moved, not of nothing to read
    still = xplane.Reduction({"devices": {"d": {"ops": ops[-3:], "modules": []}}, "host": []})
    assert pool_moved_share.read(serve_run(trace=still, pool_shapes=POOL))[0] == 0.0


@pytest.mark.parametrize("run", [
    serve_run(pool_shapes=POOL),                                       # the CPU rehearsal
    serve_run(trace=one_after_another(DECODE_HEAVY)),                  # an entry that hands no pool
    serve_run(trace=one_after_another(DECODE_HEAVY), pool_shapes=[(368,)]),
    serve_run(trace=one_after_another(DECODE_HEAVY), pool_shapes=POOL, kind="train")])
def test_pool_moved_share_with_nothing_to_read(run):
    assert pool_moved_share.read(run) is None
