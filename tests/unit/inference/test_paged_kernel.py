"""The paged Pallas kernel, interpreted, against the dense gather it stands in
for (split from ``test_inference_v2.py``: every case is an interpreted kernel
of its own shape, and a file is one worker's from start to end)."""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit.ops.compiled import compiled, dense_fallback

from .test_inference_v2 import _paged_case


def _ragged_paged_case(H, KV, T, dtype, seed=0):
    """Four sequences over a pool of 64 blocks of 16: a full chunk of T behind
    50 cached tokens, one live token behind 200, a row with no token at all,
    and half a chunk from position 0."""
    rng = np.random.default_rng(seed)
    N, Dh, NB, BS, MAXB = 4, 32, 64, 16, 20
    q = jnp.asarray(rng.normal(size=(N, T, H, Dh)), dtype)
    kpool = jnp.asarray(rng.normal(size=(NB, KV, BS, Dh)), dtype)
    vpool = jnp.asarray(rng.normal(size=(NB, KV, BS, Dh)), dtype)
    tables = jnp.asarray(rng.integers(0, NB - 1, (N, MAXB)), jnp.int32)
    n_tokens = jnp.asarray([T, 1, 0, max(T // 2, 1)], jnp.int32)
    lengths = jnp.asarray([T + 50, 201, 0, max(T // 2, 1)], jnp.int32)
    return q, kpool, vpool, tables, lengths, lengths - n_tokens, n_tokens


def _assert_kernel_is_the_fallback(case, block_size, window, slopes, atol):
    from deepspeed_tpu.ops.attention.paged import paged_attention
    q, kpool, vpool, tables, lengths, start_pos, n_tokens = case
    ref = dense_fallback(q, kpool, vpool, tables, lengths, start_pos, n_tokens,
                         1.0 / np.sqrt(q.shape[-1]), window, slopes)
    got = compiled(paged_attention, block_size=block_size, window=window, alibi_slopes=slopes)(
        q, kpool, vpool, tables, lengths, start_pos, n_tokens)
    assert got.shape == q.shape and got.dtype == q.dtype
    valid = np.asarray(jnp.arange(q.shape[1])[None, :] < n_tokens[:, None])
    got, ref = (np.asarray(a.astype(jnp.float32)) for a in (got, ref))
    np.testing.assert_allclose(got[valid], ref[valid], atol=atol)
    assert (got[~valid] == 0.0).all()  # a row that holds no token comes back exactly zero


def _parity_cases():
    yield from (pytest.param(4, 2, 4, w, a, "float32", id=i)  # the cases this test began with
                for w, a, i in [(None, False, "full"), (6, False, "window6"), (None, True, "alibi")])
    layouts = [(32, 8), (16, 16), (8, 1), (4, 2)]
    for h, kv in layouts:  # every head layout at every row count, full attention
        for t in (1, 5, 128, 256):
            yield pytest.param(h, kv, t, None, False, "float32", id=f"{h}q{kv}kv-T{t}")
    for h, kv, t in [(32, 8, 1), (32, 8, 256), (16, 16, 128), (4, 2, 5)]:
        yield pytest.param(h, kv, t, 40, False, "float32", id=f"{h}q{kv}kv-T{t}-window40")
    for h, kv, t in [(32, 8, 1), (8, 1, 5), (4, 2, 128)]:  # ALiBi with group > 1
        yield pytest.param(h, kv, t, None, True, "float32", id=f"{h}q{kv}kv-T{t}-alibi")
    for h, kv, t, w in [(32, 8, 1, None), (32, 8, 256, 40), (16, 16, 5, None), (4, 2, 128, None)]:
        yield pytest.param(h, kv, t, w, h == 4, "bfloat16", id=f"{h}q{kv}kv-T{t}-bf16pool")


@pytest.mark.parametrize("H,KV,T,window,alibi,dtype", list(_parity_cases()))
def test_paged_attention_kernel_parity(interpreted_kernels, H, KV, T, window, alibi, dtype):
    """Blocked kernel (interpret mode) == dense-gather fallback: every head
    layout the families bring (GQA, MHA, MQA, a TP shard's 2 KV heads), one row
    (decode), a verify's few, and chunks of whole row tiles, each beside a row
    of one live token and a row of none; sliding window, ALiBi where a KV
    head's group has several slopes, f32 and bf16 pools (one layer's pool: the
    rank-4 call)."""
    slopes = jnp.asarray(2.0 ** -np.arange(1, H + 1), jnp.float32) if alibi else None
    if T == 4:
        case, block_size = _paged_case(H=H, KV=KV, T=T), 8
    else:
        case, block_size = _ragged_paged_case(H, KV, T, jnp.dtype(dtype)), 16
    _assert_kernel_is_the_fallback(case, block_size, window, slopes,
                                   atol=2e-5 if dtype == "float32" else 4e-2)


def test_paged_attention_parity_with_a_kv_heads_rows_cut_into_grid_steps(
        interpreted_kernels, monkeypatch):
    """Where not even one KV head's q rows fit a grid step (MQA with many heads
    over a long chunk) ``step_tile`` cuts them into several steps: the same
    numbers, the split falling inside a live chunk and past a short one."""
    from deepspeed_tpu.ops.attention import paged
    case = _ragged_paged_case(8, 1, 128, jnp.float32)
    monkeypatch.setattr(paged, "VMEM_BUDGET_BYTES", 3 << 20)
    kvg, rows, splits, tile, _ = paged.step_tile(128, 8, 1, 32, 16, jnp.float32, jnp.float32)
    assert (kvg, splits) == (1, 2) and rows % tile == 0 and splits * rows >= 128 * 8
    _assert_kernel_is_the_fallback(case, 16, 40, None, atol=2e-5)
