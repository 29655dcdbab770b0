#!/usr/bin/env python
"""Offline full-depth ZeRO-Infinity proof: Llama-2-7B-shaped (6.74B params)
training real steps on ONE chip, params NVMe-streamed + moments in host RAM.

Writes its result as JSON to the path given with --out.  Run on its own: a
full 32-layer step streams every layer up twice, so its time is set by the
host->device link (PCIe on a TPU host).

Usage: python benchmarks/run_infinity_7b.py --out result.json [--layers 32] [--steps 1]
"""

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=32)
    ap.add_argument("--steps", type=int, default=1, help="timed steps after the warm step")
    ap.add_argument("--nvme", default="/tmp/dstpu_infinity_7b")
    ap.add_argument("--out", required=True, help="where to write the result JSON")
    args = ap.parse_args()

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.models.transformer import cross_entropy_loss, rms_norm, rotary_tables

    cfg = llama.LlamaConfig(num_layers=args.layers)  # 7B shape: 4096x11008, 32 heads
    seq, micro = 2048, 1
    D, F, L, H = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers, cfg.num_heads
    cos, sin = rotary_tables(D // H, seq, cfg.rope_theta)
    layer = llama._layer_fn(cfg, cos, sin)

    def layer_fn(p, x):
        return layer(x, p)[0]

    def stem_fn(sp, tokens):
        return sp["embed"][tokens]

    def head_fn(h, x, labels):
        x = rms_norm(x, h["final_norm"], cfg.rms_eps)
        return cross_entropy_loss(x @ h["lm_head"].astype(x.dtype), labels)

    rng = np.random.default_rng(0)
    base = lambda shape, scale: rng.standard_normal(shape, dtype=np.float32) * scale
    stacked = lambda i, o: np.broadcast_to(base((i, o), i ** -0.5), (L, i, o))
    t0 = time.time()
    params = {
        "stem": {"embed": base((cfg.vocab_size, D), 0.02)},
        "layers": {
            "attn": {"wq": stacked(D, D), "wk": stacked(D, D),
                     "wv": stacked(D, D), "wo": stacked(D, D)},
            "mlp": {"w_gate": stacked(D, F), "w_up": stacked(D, F),
                    "w_down": stacked(F, D)},
            "attn_norm": np.broadcast_to(np.ones(D, np.float32), (L, D)),
            "mlp_norm": np.broadcast_to(np.ones(D, np.float32), (L, D)),
        },
        "final_norm": np.ones(D, np.float32),
        "lm_head": base((D, cfg.vocab_size), D ** -0.5),
    }
    print(f"[{time.time()-t0:.0f}s] params built ({llama.num_params(cfg)/1e9:.2f}B)", flush=True)

    shutil.rmtree(args.nvme, ignore_errors=True)
    os.makedirs(args.nvme, exist_ok=True)
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            loss_fn=lambda p, b, r: 0.0,
            model_parameters=params,
            layer_fn=layer_fn, head_fn=head_fn, stem_fn=stem_fn,
            config={
                "train_micro_batch_size_per_gpu": micro,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-5}},
                "zero_optimization": {
                    "stage": 3,
                    "offload_param": {"device": "nvme", "nvme_path": args.nvme,
                                      "buffer_count": 24},
                    "offload_optimizer": {"device": "cpu"},
                },
                "steps_per_print": 1000,
            },
        )
        init_s = time.time() - t0
        print(f"[{init_s:.0f}s] engine init done (params on nvme)", flush=True)
        del params
        tokens = rng.integers(0, cfg.vocab_size, (micro, seq))
        batch = {"x": tokens, "y": np.roll(tokens, -1, axis=1)}
        tw = time.time()
        m = engine.train_batch(batch)
        warm_s = time.time() - tw
        print(f"[{time.time()-t0:.0f}s] warm step {warm_s:.0f}s loss={float(m.loss):.3f}", flush=True)
        ts = time.time()
        for _ in range(args.steps):
            m = engine.train_batch(batch)
        step_s = (time.time() - ts) / args.steps
        loss = float(m.loss)
        print(f"[{time.time()-t0:.0f}s] steady step {step_s:.0f}s loss={loss:.3f}", flush=True)
        out = {
            "params_b": round(llama.num_params(cfg) / 1e9, 2),
            "layers": L,
            "step_s": round(step_s, 1),
            "tok_s": round(micro * seq / step_s, 2),
            "warm_step_s": round(warm_s, 1),
            "init_s": round(init_s, 1),
            "loss": round(loss, 3),
            "loss_finite": bool(np.isfinite(loss)),
            "placement": "params:nvme moments:cpu head+stem:device",
        }
        with open(args.out, "w") as fh:
            json.dump(out, fh, indent=1)
        print(json.dumps(out), flush=True)
    finally:
        shutil.rmtree(args.nvme, ignore_errors=True)


if __name__ == "__main__":
    main()
