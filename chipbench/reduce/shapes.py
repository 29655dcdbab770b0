"""Operations and bytes that the algorithms need, counted from shapes alone.

``sizes`` is a configuration's published keys.  Recomputation (remat, a kernel
that forms the scores twice) is never counted: these are what the mathematics
requires, which is what a roofline share and an MFU are measured against."""


def _head_dim(sizes) -> int:
    return int(sizes.get("head_dim") or sizes["hidden_size"] // sizes["num_attention_heads"])


def keys_seen(position: int, window) -> int:
    """Keys the query at ``position`` (0-based) attends to under a causal
    sliding window: positions (position - window, position]."""
    return position + 1 if window is None else min(position + 1, window)


def _keys_seen_sum(first: int, count: int, window) -> int:
    """sum(keys_seen(p) for p in range(first, first + count)), in closed form."""
    last = first + count  # exclusive
    if window is None or last <= window:
        return (first + 1 + last) * count // 2
    if first >= window:
        return window * count
    ramp = window - first
    return (first + 1 + window) * ramp // 2 + window * (count - ramp)


def kv_bytes_per_token(sizes, dtype_bytes: int = 2) -> int:
    """K and V of one token over all layers."""
    return (2 * sizes["num_hidden_layers"] * sizes["num_key_value_heads"] * _head_dim(sizes)
            * dtype_bytes)


def paged_decode_bytes(sizes, prompt_len: int, new_tokens: int, dtype_bytes: int = 2) -> int:
    """KV bytes that decoding ``new_tokens`` after a prompt must read: the
    step that consumes the token at position p reads the keys_seen(p) cached
    keys and values, in every layer.  The first new token comes from prefill,
    so there are ``new_tokens - 1`` decode steps, at positions prompt_len..."""
    steps = max(new_tokens - 1, 0)
    return (_keys_seen_sum(prompt_len, steps, sizes.get("sliding_window"))
            * kv_bytes_per_token(sizes, dtype_bytes))


def prefill_attention_flops(sizes, prompt_len: int) -> int:
    """Causal, windowed attention over a whole prompt: per query, key and head
    2*Dh for the score and 2*Dh for the weighted sum, in every layer."""
    pairs = _keys_seen_sum(0, prompt_len, sizes.get("sliding_window"))
    return 4 * _head_dim(sizes) * sizes["num_attention_heads"] * sizes["num_hidden_layers"] * pairs


def prefill_attention_bytes(sizes, prompt_len: int, dtype_bytes: int = 2) -> int:
    """The least traffic of that prefill: K and V written once and read once,
    Q read and the output written once."""
    q_and_out = (2 * sizes["num_hidden_layers"] * sizes["num_attention_heads"] * _head_dim(sizes)
                 * dtype_bytes)
    return prompt_len * (2 * kv_bytes_per_token(sizes, dtype_bytes) + q_and_out)


def paged_attention_least_seconds(sizes, prompt_lens, new_tokens: int, peaks) -> dict:
    """Least time the chip could take for the attention of one wave, and the
    part of it under each bound.  Decode is bound by reading the cache; each
    prompt's prefill by the larger of its operations and its bytes."""
    flops_peak, bw = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    decode = sum(paged_decode_bytes(sizes, p, new_tokens) for p in prompt_lens) / bw
    compute = memory = 0.0
    for p in prompt_lens:
        by_flops = prefill_attention_flops(sizes, p) / flops_peak
        by_bytes = prefill_attention_bytes(sizes, p) / bw
        if by_flops >= by_bytes:
            compute += by_flops
        else:
            memory += by_bytes
    return {"seconds": decode + compute + memory, "decode_memory_s": decode,
            "prefill_compute_s": compute, "prefill_memory_s": memory}


def flash_attention_flops(sizes, batch: int, seq: int, backward: bool = True) -> int:
    """Causal attention over ``batch`` sequences of ``seq``: two matmuls
    forward (scores, weighted sum) and five backward (the scores once more, dV,
    dP, dQ, dK; a kernel that forms the scores in two passes recomputes, which
    is not counted), each 2*Dh per query, key and head, over the causal pairs
    the window leaves, in every layer."""
    pairs = _keys_seen_sum(0, seq, sizes.get("sliding_window"))
    matmuls = 2 + (5 if backward else 0)
    return (matmuls * 2 * _head_dim(sizes) * sizes["num_attention_heads"]
            * sizes["num_hidden_layers"] * pairs * batch)


def flash_attention_bytes(sizes, batch: int, seq: int, backward: bool = True,
                          dtype_bytes: int = 2) -> int:
    """Least traffic: forward reads Q, K, V and writes O; backward reads Q, K,
    V, O, dO and writes dQ, dK, dV."""
    dh, h, kv = _head_dim(sizes), sizes["num_attention_heads"], sizes["num_key_value_heads"]
    q_like, kv_like = h * dh * dtype_bytes, kv * dh * dtype_bytes
    fwd = 2 * q_like + 2 * kv_like
    bwd = 4 * q_like + 4 * kv_like
    return batch * seq * sizes["num_hidden_layers"] * (fwd + (bwd if backward else 0))


def num_matmul_params(sizes) -> int:
    """Parameters of a dense decoder that take part in a matrix
    multiplication: every projection and the output head, not the embedding
    lookup and not the norm gains.  Sizes that carry an expert count are
    refused: an FFN of experts is not ``3 * d * f`` a layer, and a count that
    is silently dense would put a wrong MFU under a right name."""
    experts = sorted(k for k in sizes if "expert" in k)
    if experts:
        raise ValueError(f"num_matmul_params counts a dense FFN and these sizes carry {experts}: "
                         "a configuration with experts brings a count of its own")
    d, f, dh = sizes["hidden_size"], sizes["intermediate_size"], _head_dim(sizes)
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layer = d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f
    return sizes["num_hidden_layers"] * layer + d * sizes["vocab_size"]


def num_params(sizes) -> int:
    """Every parameter: the matmul ones, the embedding and the norm gains."""
    d = sizes["hidden_size"]
    return (num_matmul_params(sizes) + sizes["vocab_size"] * d
            + (2 * sizes["num_hidden_layers"] + 1) * d)


def train_flops_per_token(sizes, seq: int) -> float:
    """6 per matmul parameter (2 forward, 4 backward) plus causal attention
    forward and backward (flash_attention_flops) spread over the tokens."""
    return 6 * num_matmul_params(sizes) + flash_attention_flops(sizes, 1, seq) / seq
