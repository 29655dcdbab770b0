"""Operations and bytes of latent attention (MLA), counted from shapes alone.

A cached token is one latent vector a layer, ``kv_lora_rank + qk_rope_head_dim``
values (512 + 64 for DeepSeek-V2), shared by every head; absorbed, a head's
score over it is one dot product of that width and its weighted sum runs over
the first ``kv_lora_rank`` values.  The counts are the mathematics': the
unpadded 576, whatever tile the program pads the pool to, and no expansion of
the latent to heads (``shapes.py`` counts per-head K and V and would read 20 KB
a token a layer here against 1,152 B)."""


def latent_values(sizes) -> int:
    return sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"]


def latent_bytes_per_token(sizes, dtype_bytes: int = 2) -> int:
    """One cached token in one layer."""
    return latent_values(sizes) * dtype_bytes


def pair_flops(sizes) -> int:
    """One visible (query, key) pair in one layer, all heads: 2 x width for the
    score and 2 x kv_lora_rank for the weighted sum, a head."""
    return 2 * sizes["num_attention_heads"] * (latent_values(sizes) + sizes["kv_lora_rank"])


def causal_pairs(first: int, count: int) -> int:
    """Keys seen by the ``count`` queries at positions first, first + 1, ...:
    the query at position p sees p + 1."""
    return (2 * first + count + 1) * count // 2


def decode_pairs(prompt_len: int, new_tokens: int) -> int:
    """The first new token comes from prefill: ``new_tokens - 1`` decode steps,
    the step at position p reading the p + 1 tokens cached by then."""
    return causal_pairs(prompt_len, max(new_tokens - 1, 0))


def attention_least_seconds(sizes, prompt_lens, new_tokens: int, peaks,
                            dtype_bytes: int = 2) -> dict:
    """Least time the chip could take for the latent attention of one wave, in
    every layer.  Decode: a step reads each cached token's latent once for all
    heads and multiplies every head by it (242 operations a byte: the v5e's own
    ridge, so both are counted and the larger stands).  Prefill, a prompt at a
    time: its causal pairs' operations, against the latent written once and
    read once with q in and the output out once."""
    layers, heads = sizes["num_hidden_layers"], sizes["num_attention_heads"]
    flops_peak, bw = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    q_and_out = heads * (latent_values(sizes) + sizes["kv_lora_rank"]) * dtype_bytes
    out = {"decode_compute_s": 0.0, "decode_memory_s": 0.0, "prefill_compute_s": 0.0,
           "prefill_memory_s": 0.0}
    for p in prompt_lens:
        pairs = decode_pairs(p, new_tokens)
        steps = max(new_tokens - 1, 0)
        by_flops = layers * pairs * pair_flops(sizes) / flops_peak
        by_bytes = layers * (pairs * latent_bytes_per_token(sizes, dtype_bytes)
                             + steps * q_and_out) / bw
        out["decode_compute_s" if by_flops >= by_bytes else "decode_memory_s"] += max(by_flops,
                                                                                      by_bytes)
        by_flops = layers * causal_pairs(0, p) * pair_flops(sizes) / flops_peak
        by_bytes = layers * p * (2 * latent_bytes_per_token(sizes, dtype_bytes) + q_and_out) / bw
        out["prefill_compute_s" if by_flops >= by_bytes else "prefill_memory_s"] += max(by_flops,
                                                                                        by_bytes)
    return {"seconds": sum(out.values()), **out}
