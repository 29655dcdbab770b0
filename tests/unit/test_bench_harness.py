"""bench.py harness guards — the driver artifact depends on this file
importing and gating correctly, so its pure-python machinery gets unit
coverage (the measured legs themselves run on hardware)."""

import json

import numpy as np
import pytest

import bench


def test_leg_error_keying():
    """A failing leg becomes a string under ITS OWN key (r4's artifact died
    because errors were only raised; r5 review: lambda legs lost names)."""
    def boom():
        raise RuntimeError("kaput")

    out = bench._leg("myleg", boom)
    assert set(out) == {"myleg"} and "kaput" in out["myleg"]
    assert bench._leg("ok", lambda: {"x": 1}) == {"x": 1}


def test_leg_that_raises_fails_the_run(monkeypatch):
    """The message is kept for the artifact, and main() exits non-zero on it."""
    monkeypatch.setattr(bench, "_FAILED_LEGS", [])

    def boom():
        raise RuntimeError("kaput")

    bench._leg("fine", lambda: {"x": 1})
    assert bench._FAILED_LEGS == []
    bench._leg("myleg", boom)
    assert bench._FAILED_LEGS == ["myleg"]


def test_artifact_shape_and_mfu_extraction():
    line = bench._artifact({"mfu": 0.5, "foo": 1})
    d = json.loads(line)
    assert d["value"] == 0.5 and d["vs_baseline"] == 1.25
    assert d["extra"]["foo"] == 1 and "mfu" not in d["extra"]
    assert "bench_elapsed_s" in d["extra"]


def test_serving_scenario_stall_guard():
    """A scheduler that never emits must not spin the global budget away."""
    from deepspeed_tpu.inference.v2.fastpath import ServeCounters

    class StuckEngine:
        def __init__(self):
            self.manager = type("M", (), {"seqs": {0: type("S", (), {
                "pending_tokens": 1, "done": False})()}})()
            self.counters = ServeCounters()
        def put(self, uids, prompts):
            pass
        def step(self):
            return {}
        def decode_burst(self, k, **kw):
            return None  # not fusible: the scenario must fall back to step()
        def flush(self, uid):
            pass

    tokens, dt, lats, hit_stall, link = bench._run_serving_scenario(
        StuckEngine(), [[1, 2]], {0: [0]}, max_new=4)
    assert tokens == 0 and lats == []  # bailed via the stall counter
    assert hit_stall  # and the bail is reported, not silent (ISSUE 4 review)
    assert link["host_syncs"] == 0  # nothing ever reached the device


def test_infinity_shape_ladder_budget_math():
    """The adaptive width/depth pick stays inside its budget model and the
    GQA rung's kv projection width matches llama's init (r5 review bug)."""
    import jax
    from deepspeed_tpu.models import llama
    D, F, H, KV = 2560, 6912, 20, 4  # the GQA rung
    cfg = llama.LlamaConfig(hidden_size=D, intermediate_size=F, num_heads=H,
                            num_kv_heads=KV, num_layers=2)
    p = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    assert p["layers"]["attn"]["wk"].shape == (2, D, KV * (D // H))


def test_global_budget_gating_monotone():
    assert bench._TOTAL_BUDGET_S > 0
    assert bench._remaining() <= bench._TOTAL_BUDGET_S
