"""The trace reduction on hand-built event lists, and the shape counts
against numbers worked by hand."""

import pytest

from chipbench.reduce import peaks, shapes, xplane

MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
           "num_key_value_heads": 8, "num_hidden_layers": 16, "vocab_size": 32000,
           "sliding_window": 4096}
TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 3, "vocab_size": 10, "sliding_window": 4}


def test_union_merges_overlapping_and_touching_intervals():
    assert xplane.union([(5, 9), (0, 3), (2, 4), (9, 10), (20, 21)]) == [(0, 4), (5, 10), (20, 21)]
    assert xplane.total(xplane.union([(0, 10), (2, 3), (5, 12)])) == 12


def test_busy_and_idle_share_of_a_window():
    # a while loop holding two fusions, then a gap, then a kernel
    ops = [("while.1", 0, 100), ("fusion.1", 10, 20), ("fusion.2", 40, 30),
           ("custom-call.3 | jit(f)/pallas_call[name=paged_attention]", 200, 50)]
    busy = xplane.busy_intervals(ops)
    assert busy == [(0, 100), (200, 250)]
    assert xplane.idle_gaps(busy, 0, 300) == [(100, 200), (250, 300)]
    red = xplane.Reduction({"devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
                            "host": []})
    assert red.window_s == pytest.approx(250e-9)
    assert red.busy_s == pytest.approx(150e-9)
    assert red.idle_share_by_device()["/device:TPU:0"] == pytest.approx(0.4)
    assert red.kernel_seconds("paged_attention") == pytest.approx(50e-9)


def test_time_by_name_is_self_time_and_containers_leave_the_breakdown():
    ops = [("while.1", 0, 100), ("fusion.1", 10, 20), ("fusion.1", 40, 30), ("copy.2", 200, 50)]
    assert dict(xplane.time_by_name(ops)) == {"while.1": 50, "fusion.1": 50, "copy.2": 50}
    assert xplane.time_by_name(ops + [("copy.2", 300, 5)], top=1) == [("copy.2", 55)]
    red = xplane.Reduction({"devices": {"d": {"ops": ops, "modules": []}}, "host": []})
    names = [n for n, _ in red.breakdown()["device_ops"]]
    assert "while.1" not in names and set(names) == {"fusion.1", "copy.2"}


def test_a_gap_is_named_by_the_annotation_that_covers_it():
    # device idle from 100 to 200; the host was pumping admissions 90..140,
    # dispatching (inside a longer generate span) 150..160
    host = [("chipbench.generate", 0, 400), ("admission_pump", 90, 50), ("dispatch", 150, 10)]
    assert xplane.label_gaps([(100, 200)], host) == [
        ("chipbench.generate", 50), ("admission_pump", 40), ("dispatch", 10)]
    assert xplane.label_gaps([(100, 200)], []) == [("host_unannotated", 100)]


def test_reduction_keeps_only_the_named_host_annotations():
    ops = [("fusion.1", 0, 10), ("fusion.2", 50, 10)]
    loaded = {"devices": {"d": {"ops": ops, "modules": []}},
              "host": [("dispatch", 5, 30), ("PjitFunction(f)", 0, 60)]}
    red = xplane.Reduction(loaded, annotations=("dispatch",))
    assert red.breakdown()["idle_gaps"] == [["dispatch", 25e-9], ["host_unannotated", 15e-9]]


def test_a_gap_under_nested_annotations_goes_to_the_innermost():
    # the device idles 100..200 inside one burst: the host built tables until 115,
    # blocked on the fetch 115..180 and kept its books 180..188
    ops = [("fusion.1", 0, 100), ("fusion.2", 200, 10)]
    host = [("burst", 90, 120), ("burst.prepare", 95, 20), ("burst.wait", 115, 65),
            ("burst.absorb", 180, 8), ("flush", 300, 5)]
    loaded = {"devices": {"d": {"ops": ops, "modules": []}}, "host": host}
    from chipbench.entries import serve
    assert {"burst", "burst.prepare", "burst.wait", "burst.absorb", "flush", "scatter_upload",
            "expire", "dispatch.wait"} <= set(serve.HOST_ANNOTATIONS)
    split = xplane.Reduction(loaded, serve.HOST_ANNOTATIONS).breakdown()["idle_gaps"]
    assert split == [["burst.wait", 65e-9], ["burst.prepare", 15e-9], ["burst", 12e-9],
                     ["burst.absorb", 8e-9]]
    # the spans the list does not name fall to the one around them
    whole = xplane.Reduction(loaded, ("burst",)).breakdown()["idle_gaps"]
    assert whole == [["burst", 100e-9]]


def test_exposed_collective_time():
    # an async all-gather flies 0..60 while a fusion computes 5..45: only its
    # start (5 ns) and the wait in its done (10 ns) are exposed.  A synchronous
    # reduce-scatter of 30 ns is exposed whole.
    ops = [("all-gather-start.1", 0, 5), ("fusion.7", 5, 40), ("all-gather-done.1", 50, 10),
           ("reduce-scatter.2", 100, 30), ("fusion.8", 130, 20)]
    in_flight, exposed = xplane.collective_times(ops)
    assert exposed == 5 + 10 + 30
    assert in_flight == 60 + 30
    # the TPU's own names, and a permute that only the asynchronous line shows
    ops = [("%async-collective-start (bf16[1,1024]", 0, 2), ("%fusion.1 bf16[8]", 2, 20),
           ("%async-collective-done bf16[1,4096]", 22, 3), ("%all-gather.9 bf16[4096]", 30, 4)]
    flying = [("%collective-permute-start.2 (bf16[96,1024]", 40, 10)]
    assert xplane.collective_times(ops, flying) == (25 + 4 + 10, 2 + 3 + 4)


def test_mfu_is_read_from_the_step_programs_period_in_the_trace():
    import types
    from chipbench.readers import train_mfu
    # five steps of 280 ms that start 300 ms apart, a short program between two
    # of them; the host's window (2.0 s) also held the profiler's start-up
    modules = [("jit_train_step(1)", i * 300_000_000, 280_000_000) for i in range(5)]
    modules.insert(2, ("jit__multi_slice(2)", 290_000_000, 1_000_000))
    red = xplane.Reduction({"devices": {"d": {"ops": [("fusion.1", 0, 1_480_000_000)],
                                              "modules": modules}}, "host": []})
    step = red.longest_module()
    assert step["name"] == "jit_train_step(1)" and step["runs"] == 5
    assert step["period_s"] == pytest.approx(0.3) and step["mean_s"] == pytest.approx(0.28)
    run = types.SimpleNamespace(kind="train", trace=red, sizes=MISTRAL, seq=2048, chips=4,
                                tokens_per_step=8192, tokens=5 * 8192, window_s=2.0,
                                peaks=peaks.peaks_for("TPU v5 lite"))
    value, note = train_mfu.read(run)
    by_hand = 100 * shapes.train_flops_per_token(MISTRAL, 2048) * (8192 / 0.3) / (4 * 197e12)
    assert value == pytest.approx(by_hand) and note["step_period_ms"] == 300.0
    # one run of the program: its duration stands for the period; no program line: nothing
    once = xplane.Reduction({"devices": {"d": {"ops": [("f", 0, 9)], "modules": modules[:1]}},
                             "host": []})
    assert once.longest_module()["period_s"] == pytest.approx(0.28)
    none = xplane.Reduction({"devices": {"d": {"ops": [("f", 0, 9)], "modules": []}}, "host": []})
    assert none.longest_module() is None
    assert train_mfu.read(types.SimpleNamespace(kind="train", trace=none)) is None


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        xplane.Reduction({"devices": {}, "host": [], "lines": {}})


def test_keys_seen_under_a_window():
    for first, count, window in [(0, 10, 4), (2, 10, 4), (5, 3, 4), (0, 3, None), (0, 4, 4)]:
        by_hand = sum(shapes.keys_seen(p, window) for p in range(first, first + count))
        assert shapes._keys_seen_sum(first, count, window) == by_hand


def test_paged_decode_bytes_by_hand():
    # K and V of one token, all layers: 2 x 16 layers x 8 heads x 128 x 2 B = 64 KiB
    assert shapes.kv_bytes_per_token(MISTRAL) == 65536
    # prompt 1000, 3 new tokens: 2 decode steps, reading 1001 and 1002 cached tokens
    assert shapes.paged_decode_bytes(MISTRAL, 1000, 3) == (1001 + 1002) * 65536
    # past the window every step reads 4096 tokens, no more
    assert shapes.paged_decode_bytes(MISTRAL, 5000, 3) == 2 * 4096 * 65536


def test_prefill_attention_flops_by_hand():
    # tiny: window 4, prompt 6 -> keys seen 1+2+3+4+4+4 = 18 pairs;
    # 4 x Dh(4) x heads(2) x layers(3) = 96 per pair
    assert shapes.prefill_attention_flops(TINY, 6) == 18 * 96
    # Mistral, a prompt inside the window: n(n+1)/2 pairs x 4 x 128 x 32 x 16
    assert shapes.prefill_attention_flops(MISTRAL, 1024) == (1024 * 1025 // 2) * 262144


def test_flash_forward_and_backward_by_hand():
    pairs = 2048 * 2049 // 2
    per_pair_matmul = 2 * 128 * 32 * 16  # 2 x Dh x heads x layers
    assert shapes.flash_attention_flops(MISTRAL, 1, 2048, backward=False) == 2 * per_pair_matmul * pairs
    assert shapes.flash_attention_flops(MISTRAL, 3, 2048) == 3 * 7 * per_pair_matmul * pairs
    # forward: Q and O (32 heads) + K and V (8 heads), bf16, per token and layer
    fwd = 2 * 32 * 128 * 2 + 2 * 8 * 128 * 2
    assert shapes.flash_attention_bytes(MISTRAL, 1, 2048, backward=False) == 2048 * 16 * fwd


def test_the_dense_count_refuses_sizes_with_experts():
    assert shapes.num_params(MISTRAL) == 3_620_732_928 + 32000 * 4096 + 33 * 4096
    for key in ("num_experts", "num_local_experts", "n_routed_experts"):
        with pytest.raises(ValueError, match=key):
            shapes.num_params({**MISTRAL, key: 8, "num_experts_per_tok": 2})
        with pytest.raises(ValueError, match="dense FFN"):
            shapes.train_flops_per_token({**MISTRAL, key: 8}, 2048)


def test_training_flops_are_six_n_plus_attention():
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    n = 16 * per_layer + 4096 * 32000
    assert shapes.num_matmul_params(MISTRAL) == n == 3_620_732_928
    attention = 7 * 2 * 128 * 32 * 16 * (2048 * 2049 // 2) / 2048
    assert shapes.train_flops_per_token(MISTRAL, 2048) == pytest.approx(6 * n + attention)


def test_least_time_says_which_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    least = shapes.paged_attention_least_seconds(MISTRAL, [1024], 2, v5e)
    assert least["decode_memory_s"] == pytest.approx(1025 * 65536 / 819e9)
    # a 1024-token prefill: 1.4e11 FLOPs = 0.70 ms against 0.34 ms of bytes
    assert least["prefill_compute_s"] == pytest.approx(
        shapes.prefill_attention_flops(MISTRAL, 1024) / 197e12)
    assert least["prefill_memory_s"] == 0.0
    assert least["seconds"] == pytest.approx(least["decode_memory_s"] + least["prefill_compute_s"])


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peak is recorded"):
        peaks.peaks_for("TPU v9 imaginary")
