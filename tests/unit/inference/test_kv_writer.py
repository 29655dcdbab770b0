"""The Pallas writer of a step's rows (ISSUE 32, ``ops/attention/kv_write.py``,
interpreted here) against the scatter it replaces on the chip: the same rows in
the same places, every other byte of every live block the parent's.  Only the
trash blocks may differ: the scatter parks a dead slot's row there, the writer
writes nothing.  Split from ``test_forward_paged_pool.py``, whose families and
bit-for-bit comparison it shares."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from deepspeed_tpu.compat import shard_map
from deepspeed_tpu.inference.v2 import tp as tp_rules
from deepspeed_tpu.models import deepseek_v2
from deepspeed_tpu.models.transformer import flat_chunk_indices, flat_slots, paged_chunk_indices
from deepspeed_tpu.ops.attention import kv_write as kvw
from deepspeed_tpu.parallel import MeshTopology

from .test_forward_paged_pool import FAMILIES, MAXB, PROMPTS, assert_bit_equal

W_MAXB, W_LAYERS = 6, 2


def written_both_ways(n_tokens, start_pos, t, bound, *, kvh=8, width=128, dtype=jnp.bfloat16,
                      bs=32, leaves=2, layer=1):
    """One layer's write of a ``[n, t]`` bucket (compacted where ``bound`` says
    so) into random pools by the writer and by the scatter: ``(got, want,
    before, trash rows, plan)``."""
    rng = np.random.default_rng(len(n_tokens) * 1000 + t)
    n = len(n_tokens)
    nb = n * W_MAXB + 1
    tables = jnp.asarray(rng.permutation(nb - 1)[:n * W_MAXB].reshape(n, W_MAXB), jnp.int32)
    n_tokens, start_pos = jnp.asarray(n_tokens, jnp.int32), jnp.asarray(start_pos, jnp.int32)
    pools = [jnp.asarray(rng.standard_normal((W_LAYERS * nb, kvh, bs, width)), dtype)
             for _ in range(leaves)]
    slots = flat_slots(n, t, bound)
    if slots is None:
        _, _, _, blk, off = paged_chunk_indices(jnp.zeros((n, t), jnp.int32), n_tokens, start_pos,
                                                tables, nb, bs)
    else:
        *_, blk, off = (a[None] for a in flat_chunk_indices(n_tokens, start_pos, tables, nb, bs,
                                                            slots))
    rows = [jnp.asarray(rng.standard_normal(blk.shape + (kvh, width)), dtype) for _ in pools]
    first = jnp.int32(layer * nb)
    plan = jax.jit(lambda *a: kvw.write_plan(*a, t=t, slots=slots))(pools, n_tokens, start_pos, tables)
    assert plan is not None and plan.table.shape == (5, kvw.work_bound(n, t, slots,
                                                                       kvw.tile_rows(pools)))
    got = jax.jit(lambda pools, rows: kvw.kv_write(pools, rows, first, blk, off, plan))(pools, rows)
    want = jax.jit(lambda pools, rows: kvw.kv_write(pools, rows, first, blk, off, None))(pools, rows)
    return got, want, pools, [l * nb + nb - 1 for l in range(W_LAYERS)], plan


def assert_same_outside_trash(got, want, before, trash):
    assert len(got) == len(want) == len(before)
    for g, w, b in zip(got, want, before):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w, b = (np.asarray(a.astype(jnp.float32)) for a in (g, w, b))
        live = np.ones(g.shape[0], bool)
        live[trash] = False
        np.testing.assert_array_equal(g[live], w[live])
        np.testing.assert_array_equal(g[~live], b[~live])  # the writer leaves the trash alone


# bf16 pools of 32-row blocks: a tile is 16 rows, a block two tiles
WRITES = {
    "decode-odd-and-even-offsets": ([1, 1, 1, 1], [5, 16, 31, 42], 1, None),
    "decode-in-a-bounded-bucket": ([1, 1, 1], [0, 33, 63], 1, 256),
    "decode-with-a-dead-row": ([1, 0, 1], [7, 3, 64], 1, None),
    "padded-chunk-mid-tile-to-mid-tile": ([20, 1, 0, 7], [7, 40, 0, 30], 24, None),
    "compacted-chunk-mid-tile-to-mid-tile": ([20, 1, 0, 7], [7, 40, 0, 30], 24, 32),
    "run-crosses-a-block-boundary": ([9], [28], 16, None),
    "run-covers-whole-tiles": ([48, 16], [16, 32], 48, 64),
    "run-starts-at-a-tile-and-ends-mid-tile": ([21], [32], 24, None),
    "compacted-with-dead-slots-behind": ([3, 2], [14, 31], 8, 8),
    "nothing-live": ([0, 0], [0, 5], 4, None),
}


@pytest.mark.parametrize("case", list(WRITES))
def test_the_writer_puts_the_scatters_rows_in_the_scatters_places(interpreted_kernels, case):
    n_tokens, start_pos, t, bound = WRITES[case]
    got, want, before, trash, plan = written_both_ways(n_tokens, start_pos, t, bound)
    assert_same_outside_trash(got, want, before, trash)
    # one entry a touched tile: a run of c tokens from position p lies in these
    tiles = sum((p + c - 1) // 16 - p // 16 + 1 for c, p in zip(n_tokens, start_pos) if c)
    assert int(plan.count[0]) == tiles
    if not tiles:  # nothing but what was there, the trash blocks too
        assert_bit_equal(got, before)


@pytest.mark.parametrize("kvh,width,leaves,dtype", [
    (1, 128, 2, jnp.bfloat16), (8, 128, 2, jnp.bfloat16), (16, 128, 2, jnp.bfloat16),
    (1, 640, 1, jnp.bfloat16), (8, 128, 2, jnp.float32), (1, 640, 1, jnp.float32),
    (2, 128, 2, jnp.float32)],
    ids=["mqa", "mistral-8kv", "olmoe-16kv", "latent-one-leaf", "f32-tile-of-8", "latent-f32",
         "a-tp-shard-of-2"])
def test_the_writer_reads_heads_width_leaves_and_tile_off_its_operands(interpreted_kernels, kvh, width,
                                                                       leaves, dtype):
    """A compacted chunk beside decode rows, every run unaligned, for each pool
    the families make: the tile's height is the dtype's (16 rows of bf16, 8 of
    float32) and nothing is passed for it."""
    got, want, before, trash, _ = written_both_ways(
        [21, 1, 0, 1, 9], [11, 40, 0, 63, 28], 24, 40, kvh=kvh, width=width, dtype=dtype,
        leaves=leaves)
    assert kvw.tile_rows(before) == (16 if dtype == jnp.bfloat16 else 8)
    assert_same_outside_trash(got, want, before, trash)


def test_a_block_that_is_not_whole_tiles_keeps_the_scatter(interpreted_kernels):
    pools = [jnp.zeros((W_LAYERS * 5, 2, 24, 128), jnp.bfloat16)] * 2  # 24 rows: a tile and a half
    ints = jnp.zeros(2, jnp.int32)
    assert kvw.tile_rows(pools) is None
    assert kvw.write_plan(pools, ints, ints, jnp.zeros((2, 2), jnp.int32), t=1, slots=None) is None


def scatter_instead(monkeypatch):
    """The parent's write under the same interpreted paged kernel."""
    monkeypatch.setattr(kvw, "write_plan", lambda *a, **k: None)


W_BS, W_NB = 16, 14  # a bf16 tile a block; the last block is the trash


def bounded_steps(forward, kv, rng):
    """Three chunk steps of at most 8 live tokens (what ``live_token_bound=8``
    promises) and a decode step through ``forward(kv, tokens, n_tokens,
    start_pos)``: every logits array, then the live blocks of every leaf."""
    seen, pos = [], np.zeros(len(PROMPTS), np.int32)
    for n_tokens, t in [((4, 3, 1), 4), ((4, 2, 1), 4), ((1, 0, 0), 4), ((1, 1, 1), 1)]:
        tokens = jnp.asarray(rng.integers(0, 100, (len(PROMPTS), t)), jnp.int32)
        logits, kv = forward(kv, tokens, jnp.asarray(n_tokens, jnp.int32), jnp.asarray(pos))
        seen.append(logits)
        pos = pos + np.asarray(n_tokens)
    return seen + [leaf[:, :W_NB - 1] for leaf in jax.tree_util.tree_leaves(kv)]


@pytest.mark.parametrize("family", ["llama-compacted", "llama-padded", "deepseek-v2-latent"])
def test_the_writer_through_a_familys_forward(interpreted_kernels, monkeypatch, family):
    """Chunked prefill then decode through ``forward_paged`` with the writer
    and with the scatter, the paged kernel interpreted in both: every logit
    and every live block of every leaf."""
    if family == "deepseek-v2-latent":
        module, cfg, bound = deepseek_v2, deepseek_v2.DeepseekV2Config.tiny(local_experts=4), 8
    else:
        module, cfg, bound = FAMILIES[family]
    kw = {} if bound is None else {"live_token_bound": bound}

    def run():
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                        module.init_params(cfg, jax.random.PRNGKey(1)))
        rng = np.random.default_rng(3)
        tables = jnp.asarray(rng.permutation(W_NB - 1)[:len(PROMPTS) * MAXB].reshape(-1, MAXB),
                             jnp.int32)
        kv = module.init_paged_cache(cfg, W_NB, W_BS, dtype=jnp.bfloat16)
        fwd = jax.jit(lambda kv, tokens, n_tokens, start_pos: module.forward_paged(
            cfg, params, tokens, n_tokens, start_pos, tables, kv, block_size=W_BS, **kw))
        return bounded_steps(fwd, kv, rng)

    got = run()
    scatter_instead(monkeypatch)
    assert_bit_equal(got, run())


def test_the_writer_inside_a_two_step_burst_body(interpreted_kernels, monkeypatch):
    """The pool as the carry of an outer scan of two decode steps, the writer
    inside it: tokens, logits and live blocks the scatter's."""
    module, cfg, _ = FAMILIES["llama-padded"]

    def run():
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                        module.init_params(cfg, jax.random.PRNGKey(1)))
        rng = np.random.default_rng(5)
        tables = jnp.asarray(rng.permutation(W_NB - 1)[:3 * MAXB].reshape(-1, MAXB), jnp.int32)
        kv = jax.tree_util.tree_map(
            lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
            module.init_paged_cache(cfg, W_NB, W_BS, dtype=jnp.bfloat16))
        ones = jnp.ones(3, jnp.int32)

        @jax.jit
        def burst(kv, tok0, start0):
            def body(carry, _):
                kv, tok, start = carry
                logits, kv = module.forward_paged(cfg, params, tok[:, None], ones, start, tables,
                                                  kv, block_size=W_BS)
                nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return (kv, nxt, start + 1), (nxt, logits)
            (kv, _, _), (toks, logits) = jax.lax.scan(body, (kv, tok0, start0), None, length=2)
            return toks, logits, kv

        # a burst that crosses a tile's end (15 -> 16), one mid-tile, one at a block's start
        toks, logits, kv = burst(kv, jnp.asarray([7, 8, 9], jnp.int32),
                                 jnp.asarray([15, 4, 32], jnp.int32))
        return [toks, logits, kv["k"][:, :W_NB - 1], kv["v"][:, :W_NB - 1]]

    got = run()
    scatter_instead(monkeypatch)
    assert_bit_equal(got, run())


def test_the_writer_under_tp_axis_on_two_devices(interpreted_kernels, monkeypatch):
    """Inside ``shard_map`` over a tensor axis of two host devices the writer
    sees the local KV heads of a pool sharded on them."""
    module, cfg, bound = FAMILIES["llama-compacted"]
    topo = MeshTopology.from_axis_dict({"tensor": 2, "data": -1})

    def run():
        params = module.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.default_rng(3)
        tables = jnp.asarray(rng.permutation(W_NB - 1)[:len(PROMPTS) * MAXB].reshape(-1, MAXB),
                             jnp.int32)
        kv = module.init_paged_cache(cfg, W_NB, 8, dtype=jnp.float32)  # a float32 tile a block
        p_specs = tp_rules.param_specs(module, params, 2, cfg)
        kv_specs = tp_rules.kv_pool_spec(kv, 2)
        params = tp_rules.place(topo, params, p_specs)
        kv = tp_rules.place(topo, kv, kv_specs)

        def inner(params, kv, tokens, n_tokens, start_pos):
            return module.forward_paged(cfg, params, tokens, n_tokens, start_pos, tables, kv,
                                        block_size=8, tp_axis="tensor", live_token_bound=bound)
        rep = PartitionSpec()
        fwd = jax.jit(shard_map(inner, mesh=topo.mesh, in_specs=(p_specs, kv_specs, rep, rep, rep),
                                out_specs=(rep, kv_specs), check_vma=False))
        return bounded_steps(lambda kv, *a: fwd(params, kv, *a), kv, rng)

    got = run()
    scatter_instead(monkeypatch)
    assert_bit_equal(got, run())
