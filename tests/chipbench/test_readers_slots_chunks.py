"""The readers of the metrics that read the program's slot counters and its
bucket-named programs, on hand-built inputs."""

import types

import pytest

from chipbench.readers import chunk_ms_per_ktok, slot_fill, table_fill
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
