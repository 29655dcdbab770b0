"""The paged Pallas kernel, interpreted, over a latent pool: one KV head whose
value is a prefix of its key (split from ``test_inference_v2.py`` with
``test_paged_kernel.py``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit.ops.compiled import compiled, dense_fallback


def _latent_paged_case(H, T, dk, dtype, seed=0):
    """``_ragged_paged_case`` over a latent pool: one KV head whose key is
    ``dk`` wide and whose value is the key's leading columns; no second pool."""
    rng = np.random.default_rng(seed)
    N, NB, BS, MAXB = 4, 64, 16, 20
    q = jnp.asarray(rng.normal(size=(N, T, H, dk)), dtype)
    pool = jnp.asarray(rng.normal(size=(NB, 1, BS, dk)), dtype)
    tables = jnp.asarray(rng.integers(0, NB - 1, (N, MAXB)), jnp.int32)
    n_tokens = jnp.asarray([T, 1, 0, max(T // 2, 1)], jnp.int32)
    lengths = jnp.asarray([T + 50, 201, 0, max(T // 2, 1)], jnp.int32)
    return q, pool, tables, lengths, lengths - n_tokens, n_tokens


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("H,T,dk,dv,split", [
    (8, 1, 48, 32, False), (8, 5, 48, 32, False), (8, 128, 48, 32, False),
    (16, 64, 72, 64, False), (128, 1, 72, 64, False), (16, 128, 72, 64, True)],
    ids=["decode", "verify", "chunk", "chunk-72-64", "128-heads-decode", "rows-split"])
def test_paged_attention_with_a_value_that_is_a_prefix_of_the_key(
        interpreted_kernels, monkeypatch, H, T, dk, dv, split, dtype):
    """Latent attention (MLA absorbed): key width != value width, ``vpool=None``,
    the kernel reads one tile a block and its output is ``value_dim`` wide:
    interpreted against ``_dense_fallback``, with a softmax scale that is not
    ``1 / sqrt(dk)``; a chunk whose rows ``step_tile`` cuts into equal parts."""
    from deepspeed_tpu.ops.attention import paged
    q, pool, tables, lengths, start_pos, n_tokens = _latent_paged_case(H, T, dk, jnp.dtype(dtype))
    if split:
        monkeypatch.setattr(paged, "VMEM_BUDGET_BYTES", 2 << 20)
        kvg, rows, splits, tile, _ = paged.step_tile(T, H, 1, dk, 16, q.dtype, pool.dtype, dv)
        assert kvg == 1 and splits > 1 and splits * rows == T * H  # equal parts: q is not padded
    ref = dense_fallback(q, pool, None, tables, lengths, start_pos, n_tokens, 0.21, None, None, dv)
    attend = compiled(paged.paged_attention, block_size=16, softmax_scale=0.21, value_dim=dv)  # one program
    got = attend(q, pool, None, tables, lengths, start_pos, n_tokens)
    assert got.shape == q.shape[:3] + (dv, ) and got.dtype == q.dtype
    valid = np.asarray(jnp.arange(T)[None, :] < n_tokens[:, None])
    got, ref = (np.asarray(a.astype(jnp.float32)) for a in (got, ref))
    np.testing.assert_allclose(got[valid], ref[valid], atol=2e-5 if dtype == "float32" else 4e-2)
    assert (got[~valid] == 0.0).all()
    # the columns past dv are key and never value: with them negated the scores change,
    # with q's share of them zeroed as well nothing does
    other = attend(q, pool.at[..., dv:].multiply(-1.0), None, tables, lengths, start_pos, n_tokens)
    assert not np.allclose(np.asarray(other.astype(jnp.float32))[valid], ref[valid], atol=1e-2)
    same = attend(q.at[..., dv:].set(0.0), pool.at[..., dv:].multiply(-1.0), None, tables, lengths, start_pos,
                  n_tokens)
    blind = attend(q.at[..., dv:].set(0.0), pool, None, tables, lengths, start_pos, n_tokens)
    assert np.array_equal(np.asarray(same.astype(jnp.float32)), np.asarray(blind.astype(jnp.float32)))


def test_paged_attention_refuses_a_value_pool_and_a_value_width_together(interpreted_kernels):
    from deepspeed_tpu.ops.attention import paged
    q, pool, tables, lengths, start_pos, n_tokens = _latent_paged_case(8, 1, 48, jnp.float32)
    for vpool, dv in ((pool, 32), (None, None)):
        with pytest.raises(ValueError, match="value_dim"):
            paged.paged_attention(q, pool, vpool, tables, lengths, start_pos, n_tokens,
                                  block_size=16, value_dim=dv)
