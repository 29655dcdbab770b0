"""The one file that asks the chip's compiler of the KERNELS alone (the whole
step programs are ``test_tpu_compile_programs.py``'s: under ``--dist loadfile`` a
file is one worker's from start to end): the kernels of the main path,
compiled for a described (not attached) v5e at the shapes ``chip_smoke.py``
runs — Mistral-7B heads (H=32, KV=8, Dh=128), window 4096 — and, for the paged
kernel, every shape the benchmark's four serving cells meet (OLMoE's 16 MHA
heads among them).  About 2 s a case, no chip time.  What interpret mode cannot show, this does: tiling, scalar and
vector memory limits, a kernel the compiler refuses.

The topology is described inside a module-scoped fixture (``conftest.py``), never
at import (only one process may load the TPU's library unless
``ALLOW_MULTIPLE_LIBTPU_LOAD`` says otherwise, and every xdist worker imports
every test file); compilation happens in the test's own process, with the
persistent compilation cache off around it (a described compile is written to
the cache but cannot be read back without a chip).
"""

import math

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops._pallas import kernel_calls

H, KV, DH, WINDOW = 32, 8, 128, 4096


def compile_and_count(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    return kernel_calls(compiled.as_text())


def paged_avals(chip, n, t, block, maxb, num_blocks=256, dh=DH, pool_dtype=jnp.bfloat16,
                hq=H, kvh=KV):
    return (chip((n, t, hq, dh), jnp.bfloat16),
            chip((num_blocks, kvh, block, dh), pool_dtype),
            chip((num_blocks, kvh, block, dh), pool_dtype),
            chip((n, maxb), jnp.int32), chip((n, ), jnp.int32),
            chip((n, ), jnp.int32), chip((n, ), jnp.int32))


def _cell_shapes():
    """Every kernel shape the four serving cells meet, by name from the ledger's
    ``breakdown.device_ops`` (PR 29: ``_paged_attention.<k>_bf16_<n>_<hq>_<t_pad>_128_``):
    Mistral 32q/8kv with its window, OLMoE 16q/16kv without; a decode row is t = 1."""
    mistral = [(32, 1, 12), (32, 128, 12), (32, 256, 12), (32, 1, 20), (32, 128, 20), (32, 256, 20),
               (16, 1, 12), (16, 256, 12), (4, 256, 36), (32, 5, 12)]
    for n, t, maxb in mistral:
        yield pytest.param(n, t, 128, maxb, WINDOW, H, KV, id=f"mistral-n{n}-T{t}-b{maxb}")
    for n, t, maxb in [(32, 1, 12), (32, 256, 12)]:
        yield pytest.param(n, t, 128, maxb, None, 16, 16, id=f"olmoe-n{n}-T{t}-b{maxb}")


@pytest.mark.parametrize("n,t,block,maxb,window,hq,kvh", [
    pytest.param(32, 1, 128, 40, WINDOW, H, KV, id="decode-T1-page128"),
    pytest.param(32, 1, 16, 320, WINDOW, H, KV, id="decode-T1-page16"),
    pytest.param(8, 256, 128, 40, WINDOW, H, KV, id="chunked-prefill-T256"),
    pytest.param(32, 9, 128, 40, WINDOW, H, KV, id="spec-verify-T9"),
    pytest.param(32, 1, 128, 40, None, H, KV, id="decode-no-window"),
    # the widest steps the tile chooser hands out: KV heads cut to 2 a step, and
    # an MQA group of 64 whose one KV head's rows are cut into three grid steps
    pytest.param(4, 512, 128, 12, None, 64, 8, id="chunk-T512-64q8kv"),
    pytest.param(4, 512, 128, 12, WINDOW, 64, 1, id="chunk-T512-64q1kv-rows-split"),
    pytest.param(32, 1, 128, 12, WINDOW, 8, 2, id="decode-tensor4-shard-8q2kv"),
    # four table slots a step where the tile chooser reckons them over the budget that
    # picks heads and rows and under VMEM_SLOTS_BYTES (41.0-43.5 MiB; Mistral's T = 256 below)
    pytest.param(8, 128, 128, 12, None, 64, 8, id="slots4-T128-64q8kv"),
    pytest.param(2, 1024, 128, 12, None, 16, 16, id="slots4-T1024-16q16kv"),
    pytest.param(32, 1, 128, 12, None, 64, 64, id="slots4-decode-64q64kv"),
    pytest.param(2, 1024, 128, 12, None, 12, 4, id="slots4-T1024-12q4kv"),
    pytest.param(2, 512, 128, 12, None, 16, 16, id="slots2-T512-16q16kv-at-the-allowance"),
    # a table that is no whole number of steps, and one narrower than a step
    pytest.param(32, 1, 128, 3, WINDOW, H, KV, id="decode-table-3"),
    pytest.param(8, 256, 128, 1, WINDOW, H, KV, id="chunk-table-1"),
    *_cell_shapes(),
    # Qwen3-Next's gated attention (ISSUE 43): 16 q heads over 2 KV heads of 256, a pool row two
    # lane tiles wide and a q group of 8: a decode step and a padded chunk of the whole budget
    pytest.param(8, 1, 128, 132, None, 16, 2, id="qwen3-next-decode-16q2kv-dh256"),
    pytest.param(1, 2048, 128, 132, None, 16, 2, id="qwen3-next-T2048-16q2kv-dh256"),
])
def test_paged_attention_compiles(chip, n, t, block, maxb, window, hq, kvh):
    from deepspeed_tpu.ops.attention.paged import paged_attention

    def fn(q, k, v, tables, lengths, start, n_tok):
        return paged_attention(q, k, v, tables, lengths, start, n_tok,
                               block_size=block, window=window)

    dh = 256 if (hq, kvh) == (16, 2) else DH
    avals = paged_avals(chip, n, t, block, maxb, hq=hq, kvh=kvh, dh=dh)
    assert compile_and_count(fn, *avals) == {"paged_attention": 1}


@pytest.mark.parametrize("n,t", [(8, 1), (1, 512), (8, 512), (2, 256), (8, 256), (8, 16)],
                         ids=lambda v: str(v))
def test_paged_attention_over_a_latent_pool_compiles(chip, n, t):
    """DeepSeek-V2's shapes as ``serve.mla-long-prompt`` meets them: 128 q heads
    over ONE 640-wide key (576 in whole lanes) whose first 512 columns are the
    value, decode rows and chunks up to 512 tokens (65,536 q rows, cut into
    equal parts), tables 68 wide; one kernel, no copy of the pool or of q."""
    from deepspeed_tpu.ops.attention.paged import paged_attention, step_tile

    def fn(q, pool, tables, lengths, start, n_tok):
        return paged_attention(q, pool, None, tables, lengths, start, n_tok, block_size=128,
                               softmax_scale=0.1147, value_dim=512)

    avals = (chip((n, t, 128, 640), jnp.bfloat16), chip((5 * 1024, 1, 128, 640), jnp.bfloat16),
             chip((n, 68), jnp.int32), chip((n, ), jnp.int32), chip((n, ), jnp.int32),
             chip((n, ), jnp.int32))
    compiled = jax.jit(fn).lower(*avals).compile()
    assert kernel_calls(compiled.as_text()) == {"paged_attention": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)  # nothing padded or relaid
    kvg, rows, splits, tile, _ = step_tile(t, 128, 1, 640, 128, jnp.bfloat16, jnp.bfloat16, 512)
    assert kvg == 1 and splits * rows == max(t * 128, rows)  # equal whole parts: q is not padded


@pytest.mark.parametrize("n,t,s,maxb,hq,kvh,dh,dv", [
    pytest.param(32, 256, 256, 20, H, KV, DH, None, id="mistral-n32-T256-S256"),
    pytest.param(4, 256, 256, 36, H, KV, DH, None, id="mistral-n4-T256-S256"),
    pytest.param(32, 256, 256, 12, 16, 16, DH, None, id="olmoe-n32-T256-S256"),
    pytest.param(32, 512, 512, 8, 32, 4, DH, None, id="lfm2-packed-n32-T512-S512"),
    pytest.param(4, 512, 512, 64, 128, 1, 640, 512, id="latent-n4-T512-S512"),
    pytest.param(8, 512, 512, 68, 128, 1, 640, 512, id="latent-n8-T512-S512"),
    pytest.param(8, 256, 256, 12, 8, 2, DH, None, id="tensor4-shard-8q2kv"),
    pytest.param(4, 128, 64, 12, 12, 4, DH, None, id="group-3-12q4kv"),
    pytest.param(8, 2048, 2048, 132, 16, 2, 256, None, id="qwen3-next-n8-T2048-S2048-dh256"),
    pytest.param(8, 512, 512, 132, 16, 2, 256, None, id="qwen3-next-n8-T512-S512-dh256"),
])
def test_paged_attention_on_the_flat_axis_compiles(chip, n, t, s, maxb, hq, kvh, dh, dv):
    """ISSUE 40: q as a compacted pass holds it, ``[S, H, Dh]``, at the shapes the
    five serving configurations meet: the window of a sequence's rows begins at
    an element offset read from the plan, which the compiler must see to be whole
    sublane tiles; one kernel, and nothing of the padded ``[N, T]`` size beside it
    (the temporaries are q and the output laid KV-major at the flat size, twice)."""
    from deepspeed_tpu.ops.attention.paged import flat_token_slots, paged_attention_flat, step_tile

    def fn(q, k, v, tables, lengths, start, n_tok):
        return paged_attention_flat(q, k, None if dv else v, tables, lengths, start, n_tok, chunk=t,
                                    block_size=128, window=None if dv else WINDOW,
                                    softmax_scale=0.1147 if dv else None, value_dim=dv)

    _, k, v, *ints = paged_avals(chip, n, t, 128, maxb, dh=dh, hq=hq, kvh=kvh)
    compiled = jax.jit(fn).lower(chip((s, hq, dh), jnp.bfloat16), k, v, *ints).compile()
    assert kernel_calls(compiled.as_text()) == {"paged_attention": 1}
    rows = step_tile(t, hq, kvh, dh, 128, jnp.bfloat16, jnp.bfloat16, dv)[1]
    held = flat_token_slots(n, s, hq // kvh) + -(-rows // (hq // kvh))
    flat = held * hq * (dh + (dv or dh)) * 2  # q and the output on the kernel's row axis
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * flat + (1 << 20)
    assert 4 * flat < n * t * hq * (dh + (dv or dh)) * 2 or n * t <= 4 * held


@pytest.mark.parametrize("hq,kvh", [(64, 8), (64, 1), (64, 64), (32, 8), (16, 16), (8, 2), (12, 4)])
def test_a_grid_steps_vector_memory_stays_under_the_budget(hq, kvh):
    """``step_tile`` reckons a step's VMEM from the static shapes and picks the
    KV heads (and, past one head, the row split) a step holds: under the budget
    for up to 64 q heads and chunks up to 512, all KV heads a step at decode
    and verify, never a split while a whole KV head fits."""
    from deepspeed_tpu.ops.attention import paged
    for t in (1, 5, 9, 128, 256, 512):
        for pool in (jnp.bfloat16, jnp.float32):
            kvg, rows, splits, tile, slots = paged.step_tile(t, hq, kvh, DH, 128, jnp.bfloat16, pool)
            need = paged._step_vmem_bytes(kvg, rows, tile, DH, 128, 2, jnp.dtype(pool).itemsize)
            assert need <= paged.VMEM_BUDGET_BYTES < paged.VMEM_SLOTS_BYTES < paged.VMEM_LIMIT_BYTES
            wide = paged._step_vmem_bytes(kvg, rows, tile, DH, 128, 2, jnp.dtype(pool).itemsize,
                                          None, slots)
            assert slots in paged.STEP_SLOTS and (slots == 1 or need < wide <= paged.VMEM_SLOTS_BYTES)
            assert kvh % kvg == 0 and rows % tile == 0 and splits * rows >= t * (hq // kvh)
            assert splits == 1 or kvg == 1
            if t <= 9:
                assert (kvg, splits) == (kvh, 1)


@pytest.mark.parametrize("hq,kvh,dh,t,want", [
    (71, 1, 64, 128, (1, 9216, 1)), (71, 1, 64, 256, (1, 9216, 2)), (71, 1, 64, 512, (1, 12288, 3)),
    (71, 1, 64, 1024, (1, 14592, 5)), (71, 1, 64, 4096, (1, 15360, 19)),
    (64, 8, 128, 1024, (1, 8192, 1)), (64, 8, 128, 2048, (1, 8192, 2)),
    (64, 8, 128, 4096, (1, 11008, 3)), (48, 1, 128, 2048, (1, 14080, 7)),
], ids=lambda v: str(v))
def test_rows_over_k_and_v_pools_split_at_the_first_fit(hq, kvh, dh, t, want):
    """With K and V pools the rows that pass one step are cut at the FIRST
    split that fits (PR 30's rule, its numbers): Falcon's 71 heads over one KV
    head, a prime, have no equal split short of one head a step, which would
    fetch every block 71 times.  Only a value inside the key (``dv``) prefers an
    equal split, and only one that costs at most a third more steps."""
    from deepspeed_tpu.ops.attention.paged import step_tile
    assert step_tile(t, hq, kvh, dh, 128, jnp.bfloat16, jnp.bfloat16)[:3] == want
    if hq == 71:  # the same heads over a latent pool: still never one head a step
        assert step_tile(t, hq, 1, dh, 128, jnp.bfloat16, jnp.bfloat16, dh // 2)[2] <= want[2]


def test_a_kv_block_too_large_for_a_grid_step_is_a_readable_error(chip):
    """A K and a V tile of 32k x 128 bf16, double-buffered, pass the budget
    with one KV head and one row tile: the chooser says so before the compiler."""
    from deepspeed_tpu.ops.attention.paged import paged_attention, step_tile

    def fn(q, k, v, tables, lengths, start, n_tok):
        return paged_attention(q, k, v, tables, lengths, start, n_tok, block_size=32768)

    with pytest.raises(ValueError, match=r"KV blocks of \[32768, 128\].*vector memory.*block size"):
        jax.jit(fn).lower(*paged_avals(chip, 8, 1, 32768, 4, num_blocks=8))
    step_tile(1, H, KV, DH, 8192, jnp.bfloat16, jnp.bfloat16)  # 8k blocks still fit


def test_paged_attention_alibi_compiles(chip):
    from deepspeed_tpu.ops.attention.paged import paged_attention

    def fn(q, k, v, tables, lengths, start, n_tok, slopes):
        return paged_attention(q, k, v, tables, lengths, start, n_tok,
                               block_size=128, alibi_slopes=slopes)

    avals = paged_avals(chip, 32, 1, 128, 40) + (chip((H, ), jnp.float32), )
    assert compile_and_count(fn, *avals) == {"paged_attention": 1}


def test_oversized_block_table_is_a_readable_error(chip):
    """[512, 512] int32 is what the compiler answers with "Ran out of memory in
    memory space smem. Used 1.01M of 1.00M": 128 sequences of 32k context at
    the 16-token page.  The check fires before the compiler is asked."""
    from deepspeed_tpu.ops.attention.paged import check_block_table_fits, paged_attention

    def fn(q, k, v, tables, lengths, start, n_tok):
        return paged_attention(q, k, v, tables, lengths, start, n_tok, block_size=16)

    with pytest.raises(ValueError, match=r"block table \[512, 512\].*scalar memory"):
        jax.jit(fn).lower(*paged_avals(chip, 512, 1, 16, 512))
    # the bound is the compiler's: these two it takes, as the check says
    check_block_table_fits(512, 384)
    check_block_table_fits(1000, 256)
    assert compile_and_count(fn, *paged_avals(chip, 512, 1, 16, 384)) == {"paged_attention": 1}


def flash_avals(chip, batch, seq):
    return (chip((batch, seq, H, DH), jnp.bfloat16), chip((batch, seq, KV, DH), jnp.bfloat16),
            chip((batch, seq, KV, DH), jnp.bfloat16))


def test_flash_forward_compiles_at_2k(chip):
    from deepspeed_tpu.ops.attention.flash import flash_attention
    calls = compile_and_count(lambda q, k, v: flash_attention(q, k, v, causal=True),
                              *flash_avals(chip, 2, 2048))
    assert calls == {"flash_attention_fwd": 1}


def test_flash_forward_backward_compiles_at_2k(chip):
    from deepspeed_tpu.ops.attention.flash import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(jnp.float32).sum()

    calls = compile_and_count(jax.grad(loss, argnums=(0, 1, 2)), *flash_avals(chip, 2, 2048))
    assert calls == {"flash_attention_fwd": 1, "flash_attention_bwd_dkv": 1,
                     "flash_attention_bwd_dq": 1}


def test_mistral_training_attention_reaches_flash(chip):
    """seq <= window: the window mask is the causal mask, and the Mistral
    training path must land on the flash kernel, not on a dense mask."""
    from deepspeed_tpu.models import mistral
    attn = mistral.windowed_attention(WINDOW)
    assert compile_and_count(attn, *flash_avals(chip, 2, 2048)) == {"flash_attention_fwd": 1}
    # past the window the dense mask is right, and no kernel takes it
    assert compile_and_count(attn, *flash_avals(chip, 1, WINDOW + 128)) == {}


@pytest.mark.parametrize("hk", [16, 32], ids=["two-value-heads-a-key-head", "one-value-head-a-key-head"])
@pytest.mark.parametrize("budget,n", [(512, 8), (2048, 8)], ids=lambda v: str(v))
def test_the_gated_delta_scan_compiles_at_the_cells_shapes(chip, budget, n, hk):
    """ISSUE 43: the chunked-scan kernel at Qwen3-Next's 32 value heads over 16
    key heads of 128 x 128, for a compacted pass of ``budget`` tokens over ``n``
    sequences laid on chunk edges: one Mosaic kernel, the carried matrices
    aliased in and out, nothing else held.  ISSUE 46: a grid step takes a key
    head's two value heads (v and the output ``[2, 64, 128]``, the rows ``[2, 1,
    2, 64]``, the state and its scratch ``[2, 128, 128]`` float32, one
    block-diagonal ``[128, 128]`` chain); with as many key heads as value heads
    a step is one head, as before: the same kernel, the same name."""
    from deepspeed_tpu.ops.linear_attention import gated_delta

    hv = 32
    assert gated_delta.heads_a_step(hv // hk) == (2 if hk == 16 else 1)
    chunks = gated_delta.scan_chunks(n, 0, budget)
    t = chunks * gated_delta.CHUNK
    avals = (chip((4, chunks), jnp.int32), chip((hk, t, 128), jnp.bfloat16),
             chip((hk, t, 128), jnp.bfloat16), chip((hv, t, 128), jnp.bfloat16),
             chip((hv, chunks, 2, gated_delta.CHUNK), jnp.float32), chip((n, hv, 128, 128), jnp.float32))
    compiled = jax.jit(lambda *a: gated_delta._walk_pallas(*a, rep=hv // hk, interpret=False),
                       donate_argnums=(5, )).lower(*avals).compile()
    assert kernel_calls(compiled.as_text()) == {"gdn_scan": 1}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == n * hv * 128 * 128 * 4 and memory.temp_size_in_bytes == 0


SSM_LEAF = (9 * 33, 128, 64, 128)  # serve.ssm-chat-burst's ``ssm`` leaf, flat: nine layers of 33 slots
# serve.nemotron-decode-wide's: six M layers of 65 slots, 64 heads in EIGHT B/C groups (ISSUE 62)
SSMG_LEAF, SSMG_GROUPS = (6 * 65, 64, 64, 128), 8
SSM_CELLS = {"granite": (SSM_LEAF, 1), "nemotron": (SSMG_LEAF, SSMG_GROUPS)}


@pytest.mark.parametrize("budget,n,cell", [(512, 32, "granite"), (1024, 32, "granite"), (1024, 64, "nemotron"),
                                           (2048, 64, "nemotron")], ids=lambda v: str(v))
def test_the_ssd_scan_compiles_at_the_cells_shapes(chip, budget, n, cell):
    """ISSUE 52: the chunked-scan kernel at Granite 4.0-H's 128 heads of 64 x 128
    (B and C shared by all of them), for a compacted pass of ``budget`` tokens
    over ``n`` sequences, a window of them laid on chunk edges (ISSUE 55: ``ceil(budget
    / CHUNK) + WINDOW`` chunks whatever ``n``; an empty chunk's step names the last
    live one's blocks by the table's sixth row): one Mosaic kernel.  ISSUE 53: the
    carried matrices BY REFERENCE: the cell's whole flat leaf (1.25 GB) aliased in
    and out, a chunk's slot from the prefetched table, and not one row of it (4
    MB) held beside it: this program's temporaries are x laid out anew (an entry
    parameter's 64-wide rows in whole lane tiles: twice its bytes, and no part of
    a step program, where x is made in the kernel's layout) and 0.4 MB of scalars.
    ISSUE 62: B and C a GROUP of heads, ``[G, t, Ns]``: at Nemotron-H's 64 heads in 8
    groups a grid step's 8 heads are exactly one group, whose block it names.  Each cell is
    held to ITS OWN row (Granite's 4 MB as before, Nemotron's 2 MB): B and C with their group
    axis (5-9 MB here) reach the kernel as they come, no copy of them among the temporaries."""
    from deepspeed_tpu.ops.linear_attention import ssd

    leaf, groups = SSM_CELLS[cell]
    (heads, p, ns), row = leaf[1:], math.prod(leaf[1:]) * 4  # a slot: a row's float32 matrices of one layer
    chunks = ssd.scan_chunks(n, 0, budget)
    assert chunks == budget // ssd.CHUNK + ssd.WINDOW
    t = chunks * ssd.CHUNK
    scalars = (chip((heads, chunks, ssd.CHUNK), jnp.float32), ) * 3
    avals = (chip((6, chunks), jnp.int32), chip((heads, t, p), jnp.bfloat16),
             chip((groups, t, ns), jnp.bfloat16), chip((groups, t, ns), jnp.bfloat16), scalars,
             chip(leaf, jnp.float32))
    compiled = jax.jit(lambda *a: ssd._walk_pallas(*a, interpret=False),
                       donate_argnums=(5, )).lower(*avals).compile()
    assert kernel_calls(compiled.as_text()) == {"ssd_scan": 1}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == leaf[0] * row
    assert memory.temp_size_in_bytes - 2 * heads * t * p * 2 < row


@pytest.mark.parametrize("n,cell", [(32, "granite"), (64, "nemotron"), (128, "nemotron")], ids=lambda v: str(v))
def test_the_ssd_update_compiles_at_the_cells_shapes(chip, n, cell):
    """ISSUE 52: the one-token update at a decode step of 32 rows: one Mosaic
    kernel over (row, 32 heads).  ISSUE 53: the rows' matrices BY REFERENCE: the
    whole flat leaf aliased in and out, a row's slot from the prefetched ``at``,
    under a row's 4 MB held beside it (the decays along the state's lanes: 2 MB).
    ISSUE 62: at Nemotron-H's 64 rows (and 128) of 64 heads in 8 groups a grid step's 32
    heads span FOUR groups: their B and C as the first rows of a padded contraction; the
    temporaries under ITS row of 2 MB, at 64 rows and at 128 alike."""
    from deepspeed_tpu.ops.linear_attention import ssd

    leaf, groups = SSM_CELLS[cell]
    (heads, p, ns), row = leaf[1:], math.prod(leaf[1:]) * 4
    avals = (chip((n, ), jnp.int32), chip((n, ), jnp.int32), chip((n, heads, p), jnp.bfloat16),
             chip((n, heads), jnp.float32), chip((n, groups, ns), jnp.bfloat16),
             chip((n, groups, ns), jnp.bfloat16), chip(leaf, jnp.float32))
    compiled = jax.jit(lambda *a: ssd._update_pallas(*a, interpret=False),
                       donate_argnums=(6, )).lower(*avals).compile()
    assert kernel_calls(compiled.as_text()) == {"ssd_update": 1}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == leaf[0] * row
    assert memory.temp_size_in_bytes < row


@pytest.mark.parametrize("n,t,s", [(4, 1024, 1024), (2, 512, 1024), (8, 1, None)],
                         ids=["chunk-1024", "two-chunks-of-512", "decode"])
def test_the_selection_compiles_at_the_cells_shapes(chip, n, t, s):
    """ISSUE 45: GLM-5's 32 index heads of 128 over a 132-slot table of index
    keys and its 64 heads over the 640-wide latent: the index-score kernel, the
    exact top-2,048 by bisection and the paged kernel attending the selection,
    for a compacted chunk at the swept ``token_budget`` (``s`` flat slots), a
    decode step or a burst's body ``[8, 1]``: two Mosaic
    kernels, the chip's vector and scalar memory not passed."""
    from deepspeed_tpu.ops.attention import dsa, paged

    maxb, bs, pool = 132, 128, 7 * 1024
    lead = (n, t) if s is None else (s, )
    flat = {} if s is None else {"chunk": t}

    def attend(q, q_i, w, latent, keys, tables, lengths, start, count):
        chosen = dsa.select_keys(q_i, w, keys, tables, start, count, topk=2048, **flat)
        entry = paged.paged_attention if s is None else paged.paged_attention_flat
        return entry(q, latent, None, tables, lengths, start, count, block_size=bs,
                     softmax_scale=1 / 16, value_dim=512, selection=chosen, **flat)

    avals = (chip(lead + (64, 640), jnp.bfloat16), chip(lead + (32, 128), jnp.bfloat16),
             chip(lead + (32, ), jnp.float32), chip((pool, 1, bs, 640), jnp.bfloat16),
             chip((pool, 1, bs, 128), jnp.bfloat16), chip((n, maxb), jnp.int32),
             chip((n, ), jnp.int32), chip((n, ), jnp.int32), chip((n, ), jnp.int32))
    compiled = jax.jit(attend).lower(*avals).compile()
    assert kernel_calls(compiled.as_text()) == {"dsa_index_scores": 1, "paged_attention": 1}
    # the scores, their image and the selection of 1,024 tokens over 16,896 positions, a few
    # times over: nothing of the size [tokens, heads, positions] (1,024 x 32 x 16,896 x 4 B = 2.2 GB)
    assert compiled.memory_analysis().temp_size_in_bytes < 640 << 20


@pytest.mark.parametrize("slots,top_k,width,expert_width,held,routed,layers,identity,rows", [
    (64, 12, 6144, 2048, 16, 768, 4, 256, 128), (1024, 12, 6144, 2048, 16, 768, 4, 256, 384),
    (2048, 10, 2048, 512, 128, 512, 12, None, 6400), (512, 6, 5120, 1536, 40, 160, 4, None, 1024),
    (1024, 8, 6144, 2048, 16, 256, 4, None, 640)],
    ids=["scmoe-decode", "scmoe-chunk", "gdn-chunk", "mla-chunk", "dsa-chunk"])
def test_a_shares_expert_ffn_compiles_at_the_cells_shapes(chip, slots, top_k, width, expert_width,
                                                          held, routed, layers, identity, rows):
    """ISSUE 51: one chip's share of an expert-parallel layer at the four share
    cells' shapes: the held picks compacted into a window of ``rows`` rows, three
    ``gmm`` calls over that window inside the trips' loop and the combine kernel
    (blocks of 128 x up to 2,048 and, under one tile, of a decode bucket's few
    rows); nothing as large as every pick's row is among the temporaries."""
    from deepspeed_tpu.moe.serving import expert_rows, sparse_moe_ffn
    assert expert_rows(slots, top_k, held, routed) == rows
    moe = {"gate": {"wg": chip((width, routed), jnp.bfloat16)},
           "experts": {"w_gate": chip((layers, held, width, expert_width), jnp.bfloat16),
                       "w_up": chip((layers, held, width, expert_width), jnp.bfloat16),
                       "w_down": chip((layers, held, expert_width, width), jnp.bfloat16)}}

    def layer(moe, x, live, at):
        return sparse_moe_ffn(moe, x, top_k, False, live, layer=at, identity_experts=identity)

    compiled = jax.jit(layer).lower(moe, chip((slots, width), jnp.bfloat16), chip((slots, ), jnp.bool_),
                                    chip((), jnp.int32)).compile()
    assert kernel_calls(compiled.as_text()) == {"gmm": 3, "moe_combine": 1}
    every_pick = slots * top_k * width * 2  # the bf16 rows the parent gathered a pass
    assert compiled.memory_analysis().temp_size_in_bytes < max(every_pick // 2, 4 << 20)


@pytest.mark.parametrize("slots,rows", [(64, 256), (1024, 3840)], ids=["decode", "chunk"])
def test_a_share_of_ungated_experts_compiles_at_the_cells_shapes(chip, slots, rows):
    """ISSUE 62: one chip's share of an ``E`` layer of ``serve.nemotron-decode-wide`` (64 of
    128 experts held, top 6, 2688 x 1856: neither a multiple of the grouped matmul's tiles): the
    leaves hold no ``w_gate``, so the window's FFN is TWO ``gmm`` calls with the squared
    rectifier between them (``w_up`` ``[F, D]`` multiplied transposed: laid ``[D, 1856]`` the chip
    keeps ``D`` minor and the whole 3.8 GB stack is copied for the kernel a program), the shared
    expert of the same form beside it, and the call returns the tallies the family asks for
    (held picks, held experts named); no temporary as large as every pick's row."""
    from deepspeed_tpu.moe.serving import expert_rows, sparse_moe_ffn
    width, expert_width, held, routed, layers, top_k = 2688, 1856, 64, 128, 6, 6
    assert expert_rows(slots, top_k, held, routed) == rows
    moe = {"gate": {"wg": chip((width, routed), jnp.bfloat16), "bias": chip((routed, ), jnp.bfloat16)},
           "experts": {"w_up": chip((layers, held, expert_width, width), jnp.bfloat16),
                       "w_down": chip((layers, held, expert_width, width), jnp.bfloat16)},
           "shared": {"w_up": chip((2 * expert_width, width), jnp.bfloat16),
                      "w_down": chip((2 * expert_width, width), jnp.bfloat16)}}

    def layer(moe, x, live, at):
        return sparse_moe_ffn(moe, x, top_k, True, live, layer=at, scaling=2.5, scoring="sigmoid",
                              norm_eps=1e-20, tally=("held", "experts_hit"))

    compiled = jax.jit(layer).lower(moe, chip((slots, width), jnp.bfloat16), chip((slots, ), jnp.bool_),
                                    chip((), jnp.int32)).compile()
    assert kernel_calls(compiled.as_text()) == {"gmm": 2, "moe_combine": 1}
    every_pick = slots * top_k * width * 2
    assert compiled.memory_analysis().temp_size_in_bytes < max(every_pick, 4 << 20)


def test_fused_adamw_flat_compiles(chip):
    from deepspeed_tpu.ops.adam.fused_adam import fused_adamw_flat
    n = 1 << 26  # one stacked 4096 x 14336 FFN leaf is 2^25.8 elements

    def fn(p, m, v, g):
        return fused_adamw_flat(p, m, v, g, lr=1e-4, step=3)

    calls = compile_and_count(fn, chip((n, ), jnp.float32), chip((n, ), jnp.float32),
                              chip((n, ), jnp.float32), chip((n, ), jnp.bfloat16))
    assert calls == {"fused_adamw_kernel": 1}


def test_quantize_int8_compiles(chip):
    from deepspeed_tpu.ops.quantizer.quantize import quantize_int8
    calls = compile_and_count(lambda x: quantize_int8(x)[:2], chip((4096, 14336), jnp.bfloat16))
    assert sum(calls.values()) == 1


KDA_LEAF = (10 * 17, 32, 128, 128)  # serve.kda-mixed-lengths's ``recurrent`` leaf, flat: ten layers of 17 slots
KDA_ROW = 32 * 128 * 128 * 4        # one slot of it: a row's float32 matrices of one layer, 2 MB


@pytest.mark.parametrize("budget", [1024, 2048])
def test_the_kda_scan_compiles_at_the_cells_shapes(chip, budget):
    """ISSUE 58: the chunked-scan kernel at Ling-3.0's 32 heads of 128 x 128 with a decay a
    channel (``gamma`` ``[heads, C, 128]`` float32 a chunk, the factors of A and B a block of
    16 rows at a time), for a compacted pass of ``budget`` tokens, a window of its rows laid
    on chunk edges: one Mosaic kernel, the carried matrices BY REFERENCE: the cell's whole
    flat leaf (0.36 GB) aliased in and out, a chunk's slot from the prefetched table, and
    not one row of it (2 MB) held beside it."""
    from deepspeed_tpu.ops.linear_attention import kda, ssd

    heads, dk, dv = KDA_LEAF[1:]
    chunks = ssd.scan_chunks(16, 0, budget)
    assert chunks == budget // kda.CHUNK + ssd.WINDOW
    t = chunks * kda.CHUNK
    avals = (chip((6, chunks), jnp.int32), chip((heads, t, dk), jnp.bfloat16),
             chip((heads, t, dk), jnp.bfloat16), chip((heads, t, dv), jnp.bfloat16),
             chip((heads, t, dk), jnp.float32), chip((heads, chunks, kda.CHUNK), jnp.float32),
             chip(KDA_LEAF, jnp.float32))
    compiled = jax.jit(lambda *a: kda._walk_pallas(*a, interpret=False),
                       donate_argnums=(6, )).lower(*avals).compile()
    assert kernel_calls(compiled.as_text()) == {"kda_scan": 1}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == KDA_LEAF[0] * KDA_ROW
    assert memory.temp_size_in_bytes < KDA_ROW


def test_the_kda_update_compiles_at_the_cells_shapes(chip):
    """ISSUE 58: the one-token update at a decode step of 16 rows: one Mosaic kernel over
    (row, 8 heads), the rows' matrices BY REFERENCE: the whole flat leaf aliased in and out,
    a row's slot from the prefetched ``at``, under a row's 2 MB held beside it."""
    from deepspeed_tpu.ops.linear_attention import kda

    n, (heads, dk, dv) = 16, KDA_LEAF[1:]
    avals = (chip((n, ), jnp.int32), chip((n, ), jnp.int32), chip((n, heads, 4, dk), jnp.float32),
             chip((n, heads, 1, dv), jnp.float32), chip(KDA_LEAF, jnp.float32))
    compiled = jax.jit(lambda *a: kda._update_pallas(*a, interpret=False),
                       donate_argnums=(4, )).lower(*avals).compile()
    assert kernel_calls(compiled.as_text()) == {"kda_update": 1}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == KDA_LEAF[0] * KDA_ROW and memory.temp_size_in_bytes < KDA_ROW
