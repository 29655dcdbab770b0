"""Shapes and counts of latent attention over a learned selection of the cache
(DeepSeek sparse attention; GLM-5's ``glm_moe_dsa``), from the published sizes,
the leaf shapes of the pool the engine holds and the engine's ``dsa_*``
counters: which results of a device trace are certainly the indexer's, and the
least time the chip could take for the attention over the SELECTED pairs and for
the index scores over the causal ones.  Nothing here comes from ``deepspeed_tpu``.

The pool has two leaves ``[L, NB, 1, bs, width]``: the latent (``kv_lora_rank +
qk_rope_head_dim`` in whole lanes) and the index keys (``index_head_dim``)."""

VALUE_BYTES = 2  # every serving configuration's pool and activations are bfloat16
SCORE_DTYPES = ("f32", "u32", "s32", "pred")  # index scores, their ordered image, counts, masks


def is_family(sizes) -> bool:
    return "index_topk" in sizes and "index_n_heads" in sizes and "kv_lora_rank" in sizes


def block_size(pool_shapes):
    """The pool's block size, from its rank-5 leaves; None where they disagree or are none."""
    sizes = {s[3] for s in pool_shapes or () if len(s) == 5}
    return sizes.pop() if len(sizes) == 1 else None


def attention_pair_operations(sizes) -> int:
    """Operations of one (query token, key) pair in one layer, absorbed as the
    mathematics allows at the least: every head's score over the cached vector
    (``kv_lora_rank + qk_rope_head_dim``) and its weighted sum over the latent
    (``kv_lora_rank``); a multiply-add is two."""
    return 2 * sizes["num_attention_heads"] * (2 * sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])


def index_pair_operations(sizes) -> int:
    """Operations of one (query token, key) pair of the index scores in one layer."""
    return 2 * sizes["index_n_heads"] * sizes["index_head_dim"]


def is_selection_result(dtype, dims, sizes, bs) -> bool:
    """A result that is certainly the indexer's scores or the selection made of
    them: a rank-2 ``[tokens, positions]`` of float32 scores, their unsigned
    image, counts or masks whose positions are whole blocks and more than
    ``index_topk`` of them (an activation is bfloat16, or its last axis is a
    width of the model and no multiple of the block size that large); the
    selection laid out for the kernel ``[groups, steps, 8, keys]``; and the
    bisection's thresholds and counts ``[tokens, 1]`` of unsigned or signed
    integers.  NOT among them, because nothing tells them from the step's other
    per-token operations: the indexer's three projections and its LayerNorm."""
    dims = tuple(dims)
    if dtype not in SCORE_DTYPES or not dims or not bs:
        return False
    widths = {sizes[k] for k in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                                 "q_lora_rank", "vocab_size") if k in sizes}
    if len(dims) == 2 and dims[1] == 1 and dtype in ("u32", "s32"):
        return True
    if len(dims) == 2 and dims[1] % bs == 0 and dims[1] > sizes["index_topk"]:
        return dtype != "f32" or dims[1] not in widths
    return len(dims) == 4 and dims[2] == 8 and dims[3] % bs == 0 and dtype == "f32"


def attention_least_seconds(sizes, selected_keys: int, query_tokens: int, peaks) -> dict:
    """The least time for the attention over ``selected_keys`` (query token,
    key) pairs (the engine's ``dsa_selected_keys``: tokens x layers x keys
    attended) of ``query_tokens`` (tokens x layers) queries: the pairs'
    operations at the bf16 peak, against q in and the output out at 2 bytes a
    value.  The selected latent rows' reads are LEFT OUT of the bytes: how many
    query tokens share a read is the implementation's (a row a token at 1,152 B
    would be 2.4 MB a token a layer, a row a pass almost nothing), so the share
    is the smaller for it and the operations bound it."""
    heads, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    operations = selected_keys * attention_pair_operations(sizes)
    moved = query_tokens * heads * (2 * rank + sizes["qk_rope_head_dim"]) * VALUE_BYTES
    compute_s = operations / peaks["bf16_flops_per_s"]
    memory_s = moved / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute_s, memory_s), "compute_s": compute_s, "memory_s": memory_s}


def index_least_seconds(sizes, causal_keys: int, tokens_a_pass: float, peaks) -> dict:
    """The least time for the index scores over ``causal_keys`` (query token,
    key) pairs (the engine's ``dsa_causal_keys``): the pairs' operations at the
    bf16 peak, against each cached index key read once a pass (a pass's
    ``tokens_a_pass`` query tokens share the read) at 2 bytes a value."""
    operations = causal_keys * index_pair_operations(sizes)
    moved = causal_keys / max(tokens_a_pass, 1.0) * sizes["index_head_dim"] * VALUE_BYTES
    compute_s = operations / peaks["bf16_flops_per_s"]
    memory_s = moved / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute_s, memory_s), "compute_s": compute_s, "memory_s": memory_s}
