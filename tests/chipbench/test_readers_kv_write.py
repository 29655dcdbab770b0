"""``kv.write_share``'s reader on hand-built traces: it counts the events
named ``kv_write`` and no other, gives nothing (and does not raise) where there
is no trace or no such event (the parent commit, a train cell); and the readers
that find the paged kernel by ``paged_attention`` in an event's name do not
count the writer as attention."""

import types

import pytest

from chipbench.readers import (kv_write_share, mla_kernel_share, paged_attention_roofline,
                               pool_moved_share)
from chipbench.reduce import xplane

US = 1_000_000  # ns in the unit of the durations below (a millisecond)
MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336, "num_hidden_layers": 16,
           "num_attention_heads": 32, "num_key_value_heads": 8}
MLA = {"hidden_size": 5120, "num_hidden_layers": 5, "num_attention_heads": 128,
       "kv_lora_rank": 512, "qk_rope_head_dim": 64}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# a chunk pass and a decode step of a 16-layer stack of 368 blocks, as the trace
# names them: the writer's result is the tuple of the two aliased pools
WRITE = "%kv_write.3 = (bf16[5888,8,128,128]{3,2,1,0:T(8,128)(2,1)}, bf16[5888,8,128,128]{3,2,1,0}) custom-call(...)"
KERNEL = "%paged_attention.4 = bf16[4,8,1024,128]{3,2,1,0} custom-call(...)"
CHUNK = [(WRITE, 12), (KERNEL, 230), ("%fusion.146 = bf16[256,4096]{1,0} fusion(...)", 300),
         (WRITE, 14), (KERNEL, 226)]
DECODE = [(WRITE.replace(".3", ".7"), 9), (KERNEL.replace("[4,8,1024,", "[32,8,16,"), 180),
          ("%fusion.12 = bf16[32,1,4096]{2,1,0} fusion(...)", 500)]
PARENT = [("%fusion.146 = bf16[6029312,128]{1,0} fusion(...)", 141), (KERNEL, 230)]


def trace_of(*programs, devices=1):
    tree = {"devices": {}, "host": []}
    for d in range(devices):
        ops, modules = [], []
        for i, (body, name) in enumerate(programs):
            t = 10_000 * i * US
            modules.append((f"jit_{name}(1)", t, 6000 * US))
            for op, dur_us in body:
                ops.append((xplane.short_name(op), t, dur_us * US))
                t += dur_us * US
        tree["devices"][f"/device:TPU:{d}"] = {"ops": ops, "modules": modules}
    return xplane.Reduction(tree)


def serve_run(**fields):
    fields = {"kind": "serve", "trace": None, "counters": {}, "sizes": MISTRAL, "peaks": PEAKS,
              "lengths": [2048, 4096], "max_new_tokens": 32,
              "pool_shapes": [(16, 368, 8, 128, 128)], **fields}
    return types.SimpleNamespace(**fields)


def test_the_writers_events_are_counted_and_the_kernels_are_not():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n4_t256_b36"), (DECODE, "burst_n32_k16")))
    value, note = kv_write_share.read(run)
    assert note["calls"] == 3 and note["write_s"] == pytest.approx(35e-3, abs=1e-4)
    assert note["us_per_call"] == pytest.approx(35e3 / 3, abs=1e-2)
    assert value == pytest.approx(100 * 35e-3 / run.trace.busy_s)
    assert run.trace.busy_s == pytest.approx((12 + 230 + 300 + 14 + 226 + 9 + 180 + 500) * 1e-3)


def test_on_two_devices_calls_and_seconds_are_one_devices():
    run = serve_run(trace=trace_of((CHUNK, "fwd_n4_t256_b36"), devices=2))
    _, note = kv_write_share.read(run)
    assert note["calls"] == 2 and note["write_s"] == pytest.approx(26e-3, abs=1e-4)


@pytest.mark.parametrize("run", [
    serve_run(), serve_run(trace=trace_of((PARENT, "fwd_n4_t256_b36"))),
    serve_run(kind="train", trace=trace_of((CHUNK, "train_step")))],
    ids=["no-trace", "the-parents-scatter", "a-train-cell"])
def test_without_a_trace_or_without_the_event_it_gives_nothing(run):
    assert kv_write_share.read(run) is None


def test_the_readers_of_the_paged_kernel_do_not_count_the_writer():
    with_writer = trace_of((CHUNK, "fwd_n4_t256_b36"), (DECODE, "burst_n32_k16"))
    without = trace_of(([op for op in CHUNK if op[0] is not WRITE], "fwd_n4_t256_b36"),
                       (DECODE[1:], "burst_n32_k16"))
    assert "paged_attention" not in kv_write_share.KERNEL
    for trace in (with_writer, without):
        assert trace.kernel_seconds("paged_attention") == pytest.approx((230 + 226 + 180) * 1e-3)
    got = paged_attention_roofline.read(serve_run(trace=with_writer))
    want = paged_attention_roofline.read(serve_run(trace=without))
    assert got == want and got[1]["kernel_s"] == pytest.approx(636e-3, abs=1e-4)
    mla = dict(sizes=MLA, pool_shapes=[(5, 1024, 1, 128, 640)])
    got = mla_kernel_share.read(serve_run(trace=with_writer, **mla))
    want = mla_kernel_share.read(serve_run(trace=without, **mla))
    assert got[1]["kernel_s"] == want[1]["kernel_s"] == pytest.approx(636e-3, abs=1e-4)
    # nor is the writer's result, the whole stack, what pool.moved_share calls pool-shaped
    assert pool_moved_share.read(serve_run(trace=with_writer))[0] == 0.0
