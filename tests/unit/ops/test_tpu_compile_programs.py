"""What ``test_tpu_compile.py`` asks of the kernels, asked of whole step programs:
every family's ``forward_paged`` at its published widths, compiled for the
described v5e as a decode step, a chunk and a burst's body."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops._pallas import kernel_calls

from .test_tpu_compile import DH, H, KV, WINDOW


def mistral_shapes(chip, layers):
    """Mistral-7B at its published widths and ``layers`` layers (every layer is
    one scan body) with the serving cells' pool of 368 blocks of 128, as shapes
    on the described chip: ``(config, params, kv)``."""
    from deepspeed_tpu.models import mistral
    cfg = mistral.MistralConfig(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                                num_layers=layers, num_heads=H, num_kv_heads=KV,
                                max_seq_len=32768, sliding_window=WINDOW)
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, jnp.bfloat16), tree)
    return (cfg, on_chip(jax.eval_shape(lambda: mistral.init_params(cfg, jax.random.PRNGKey(0)))),
            on_chip(jax.eval_shape(lambda: mistral.init_paged_cache(cfg, 368, 128))))


@pytest.mark.parametrize("n,t,b", [(32, 256, 20), (4, 256, 36)],
                         ids=["chat-burst-n32", "long-prompt-n4"])
def test_compacted_ragged_forward_compiles_and_holds_no_tensor_more_than_padded(chip, n, t, b):
    """The Mistral ragged forward at its published widths (two layers; every
    layer is one scan body) over a mixed SplitFuse bucket: compacted onto 256
    flat slots (ISSUE 25) it compiles for the v5e, still calls the one paged
    kernel, and holds not one tensor more than the padded program.  Since the
    pool is carried in place (ISSUE 28) a program's temporaries are a megabyte
    where its activations fit the logits' buffer (n = 4: 1,160,704 bytes
    compacted, 1,064,960 padded) and how the compiler packs the index vectors
    decides the rest: 94 KiB more here, and not in proportion to the slots.  So
    the guard is one tensor: the excess stays under the smallest array the
    per-token layers make, a step's K rows ``[S, KV, Dh]`` (512 KiB).  At
    n = 32 the compacted program holds 161 MiB less."""
    from deepspeed_tpu.models import mistral
    cfg, params, kv = mistral_shapes(chip, layers=2)
    ints = [chip(shape, jnp.int32) for shape in ((n, t), (n, ), (n, ), (n, b))]
    held = {}
    for bound in (256, None):
        def fwd(params, kv, tokens, n_tokens, start_pos, tables):
            return mistral.forward_paged(cfg, params, tokens, n_tokens, start_pos, tables, kv,
                                         block_size=128, live_token_bound=bound)
        compiled = jax.jit(fwd, donate_argnums=(1, )).lower(params, kv, *ints).compile()
        assert kernel_calls(compiled.as_text()) == {"paged_attention": 1, "kv_write": 1}
        m = compiled.memory_analysis()
        held[bound] = (m.argument_size_in_bytes + m.output_size_in_bytes
                       + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert held[256] - held[None] < 256 * KV * DH * 2


def results(compiled_text):
    """``(opcode, result types, [dims of each result])`` of every instruction of
    an optimised HLO text but those that only hand a buffer on."""
    for line in compiled_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if m is None or m.group(2) in ("parameter", "get-tuple-element", "tuple", "bitcast",
                                       "while", "conditional", "call"):
            continue
        yield m.group(2), m.group(1), [[int(d) for d in dims.split(",")]
                                       for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1))]


def pool_shaped_results(compiled_text, pool_shape):
    """``(opcode, shape)`` of every instruction of an optimised HLO text with a
    result shaped like the pool: whole layers of it (one, or all L) with the
    head dimension last, however the dimensions before are folded
    (``[L,NB,KV,bs,Dh]``, ``[NB,KV,bs,Dh]``, ``[L*NB*KV*bs,Dh]``)."""
    layer = int(np.prod(pool_shape[1:]))
    return [(opcode, types) for opcode, types, shapes in results(compiled_text)
            if any(dims[-1] == pool_shape[-1] and int(np.prod(dims)) % layer == 0 for dims in shapes)]


def burst_of(fwd):
    """``fwd`` inside a two-step scan that carries the cache and picks greedily, as a burst runs it."""
    def burst(params, kv, tokens, n_tokens, start_pos, tables):
        def body(carry, _):
            kv, tok, start = carry
            logits, kv = fwd(params, kv, tok, n_tokens, start, tables)
            return (kv, jnp.argmax(logits, axis=-1).astype(jnp.int32), start + 1), tok
        (kv, _, _), toks = jax.lax.scan(body, (kv, tokens, start_pos), None, length=2)
        return toks, kv
    return burst


def olmoe_shapes(chip, layers):
    """OLMoE-1B-7B at its published widths (16 MHA heads: 16 KV heads a block,
    64 experts) over the serving cells' pool of 368 blocks of 128."""
    from deepspeed_tpu.models import olmoe
    cfg = olmoe.OlmoeConfig(num_layers=layers)
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, jnp.bfloat16), tree)
    return (olmoe, cfg,
            on_chip(jax.eval_shape(lambda: olmoe.init_params(cfg, jax.random.PRNGKey(0)))),
            on_chip(jax.eval_shape(lambda: olmoe.init_paged_cache(cfg, 368, 128))))


def deepseek_v2_shapes(chip, layers):
    """DeepSeek-V2 as ``serve.mla-long-prompt`` holds it (40 of 160 experts, a
    quarter of the vocabulary; ``layers`` = the dense layer and ``layers - 1``
    expert layers, a scan each) over its latent pool of 1,024 blocks: one leaf
    ``[L, 1024, 1, 128, 640]``."""
    from deepspeed_tpu.models import deepseek_v2
    cfg = deepseek_v2.DeepseekV2Config(vocab_size=25600, num_layers=layers, num_local_experts=40)
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, jnp.bfloat16), tree)
    return (deepseek_v2, cfg,
            on_chip(jax.eval_shape(lambda: deepseek_v2.init_params(cfg, jax.random.PRNGKey(0)))),
            on_chip(jax.eval_shape(lambda: deepseek_v2.init_paged_cache(cfg, 1024, 128))))


def lfm2_shapes(chip, layers):
    """LFM2-24B-A2B as ``serve.conv-chat-burst`` holds it (``layers`` = 10: both
    dense conv layers and two periods of attention, conv, conv, conv; 64
    experts): a pool of the two attention layers alone, two 64-wide KV heads a
    row ``[2, 1024, 4, 128, 128]``, and the conv state ``[8, 33, 2, 2048]``."""
    from deepspeed_tpu.models import lfm2
    cfg = lfm2.Lfm2Config(num_layers=layers)
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, jnp.bfloat16), tree)
    return (lfm2, cfg,
            on_chip(jax.eval_shape(lambda: lfm2.init_params(cfg, jax.random.PRNGKey(0)))),
            on_chip(jax.eval_shape(lambda: lfm2.init_paged_cache(cfg, 1024, 128))))


def qwen3_next_shapes(chip, layers):
    """Qwen3-Next-80B-A3B as ``serve.gdn-long-prompt`` holds it (128 of 512
    experts, a quarter of the vocabulary; ``layers`` = 8: two periods of three
    Gated DeltaNet layers and a gated attention, one scan): a pool of the
    attention layers alone at heads of 256 ``[2, 800, 2, 128, 256]`` and the
    DeltaNet layers' state, two leaves of eight slots and a trash slot: the
    shift ``[6, 9, 3, 8192]`` and the float32 matrices ``[6, 9, 32, 128, 128]``."""
    from deepspeed_tpu.models import qwen3_next
    cfg = qwen3_next.Qwen3NextConfig(vocab_size=37984, num_layers=layers, num_local_experts=128)
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, jnp.bfloat16), tree)
    kv = jax.eval_shape(lambda: qwen3_next.init_paged_cache(cfg, 800, 128, state_slots=8))
    return (qwen3_next, cfg,
            on_chip(jax.eval_shape(lambda: qwen3_next.init_params(cfg, jax.random.PRNGKey(0)))),
            jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), kv))


def glm_moe_dsa_shapes(chip, layers):
    """GLM-5 as ``serve.dsa-long-prompt`` holds it (16 of 256 experts, an eighth
    of the vocabulary; ``layers`` = the three dense layers and ``layers - 3``
    expert layers, a scan each) over its pool of 1,024 blocks: TWO leaves of
    unlike widths, the index keys ``[L, 1024, 1, 128, 128]`` and the latent
    ``[L, 1024, 1, 128, 640]``."""
    from deepspeed_tpu.models import glm_moe_dsa
    cfg = glm_moe_dsa.GlmMoeDsaConfig(vocab_size=19360, num_layers=layers, num_local_experts=16)
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, jnp.bfloat16), tree)
    return (glm_moe_dsa, cfg,
            on_chip(jax.eval_shape(lambda: glm_moe_dsa.init_params(cfg, jax.random.PRNGKey(0)))),
            on_chip(jax.eval_shape(lambda: glm_moe_dsa.init_paged_cache(cfg, 1024, 128))))


def granite_shapes(chip, layers):
    """Granite 4.0-H-Small as ``serve.ssm-chat-burst`` holds it (36 of 72 experts,
    half the vocabulary; ``layers`` = 10: one period, mamba x 5, attention, mamba
    x 4, three scans): a pool of the one attention layer ``[1, 1024, 8, 128, 128]``
    and the Mamba-2 layers' state, 32 slots and a trash slot: the shift ``[9, 33,
    3, 8448]`` and the float32 matrices ``[9, 33, 128, 64, 128]`` (1.25 GB)."""
    from deepspeed_tpu.models import granite_moe_hybrid
    cfg = granite_moe_hybrid.GraniteMoeHybridConfig(vocab_size=50176, num_layers=layers,
                                                    num_local_experts=36)
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, jnp.bfloat16), tree)
    kv = jax.eval_shape(lambda: granite_moe_hybrid.init_paged_cache(cfg, 1024, 128))
    return (granite_moe_hybrid, cfg,
            on_chip(jax.eval_shape(lambda: granite_moe_hybrid.init_params(cfg, jax.random.PRNGKey(0)))),
            jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), kv))


def ling_shapes(chip, layers):
    """Ling-3.0-flash as ``serve.kda-mixed-lengths`` holds it (64 of 512 experts, an
    eighth of the vocabulary; ``layers`` = 12: two dense KDA layers, then KDA x 3, MLA, KDA
    x 5, MLA, five scans): the latent pool of the two MLA layers ``[2, 1024, 1, 128,
    640]`` and the KDA layers' state, 16 slots and a trash slot: the shift ``[10, 17,
    3, 12288]`` and the float32 matrices ``[10, 17, 32, 128, 128]`` (0.36 GB)."""
    from deepspeed_tpu.models import bailing_hybrid
    cfg = bailing_hybrid.BailingHybridConfig(vocab_size=19648, num_layers=layers, num_local_experts=64)
    params = jax.eval_shape(lambda: bailing_hybrid.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    kv = jax.eval_shape(lambda: bailing_hybrid.init_paged_cache(cfg, 1024, 128, state_slots=16))
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), tree)
    return bailing_hybrid, cfg, on_chip(params), on_chip(kv)


def nemotron_shapes(chip, layers):
    """Nemotron-3-Nano as ``serve.nemotron-decode-wide`` holds it (64 of 128 experts, half
    the vocabulary; ``layers`` = 14: ``MEMEM*E`` twice, one scan of two periods): a pool
    of the two ``*`` layers ``[2, 1024, 2, 128, 128]``, the six ``M`` layers' state, 64
    slots and a trash slot (the shift ``[6, 65, 3, 6144]`` and the float32 matrices ``[6,
    65, 64, 64, 128]``, 0.82 GB) and the pick tallies ``[3]``: an ``E`` layer has a row in none."""
    from deepspeed_tpu.models import nemotron_h
    cfg = nemotron_h.NemotronHConfig(vocab_size=65536, num_layers=layers, held_experts=64)
    params = jax.eval_shape(lambda: nemotron_h.init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    kv = jax.eval_shape(lambda: nemotron_h.init_paged_cache(cfg, 1024, 128, state_slots=64))
    on_chip = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), tree)
    return nemotron_h, cfg, on_chip(params), on_chip(kv)


def mistral_module_and_shapes(chip, layers):
    from deepspeed_tpu.models import mistral
    return (mistral, ) + mistral_shapes(chip, layers)


IN_PLACE = {  # model, (n, t, live_token_bound), a burst's scan around it
    "decode": (mistral_module_and_shapes, (16, 1, 256), False),
    "compacted": (mistral_module_and_shapes, (16, 64, 64), False),
    "padded-chunk": (mistral_module_and_shapes, (4, 64, None), False),
    "burst": (mistral_module_and_shapes, (16, 1, None), True),
    "olmoe-16kv-decode": (olmoe_shapes, (32, 1, 256), False),
    "olmoe-16kv-compacted": (olmoe_shapes, (32, 256, 256), False),
    "deepseek-v2-latent-decode": (deepseek_v2_shapes, (8, 1, 512), False),
    "deepseek-v2-latent-compacted": (deepseek_v2_shapes, (3, 512, 512), False),
    "lfm2-packed-heads-decode": (lfm2_shapes, (32, 1, 512), False),
    "lfm2-packed-heads-compacted": (lfm2_shapes, (32, 256, 256), False),
    "lfm2-packed-heads-burst": (lfm2_shapes, (32, 1, None), True),
    "qwen3-next-state-tree-decode": (qwen3_next_shapes, (8, 1, 2048), False),
    "qwen3-next-state-tree-compacted": (qwen3_next_shapes, (8, 512, 512), False),
    "qwen3-next-state-tree-burst": (qwen3_next_shapes, (8, 1, None), True),
    "glm-5-two-leaves-decode": (glm_moe_dsa_shapes, (8, 1, 512), False),
    "glm-5-two-leaves-compacted": (glm_moe_dsa_shapes, (4, 1024, 1024), False),
    "glm-5-two-leaves-burst": (glm_moe_dsa_shapes, (8, 1, None), True),
}


# layers (every layer of a stack is one scan body: the count sets the pool's
# size alone) and layer scans of each model's program
LAYERS_AND_SCANS = {mistral_module_and_shapes: (3, 1), olmoe_shapes: (2, 1),
                    deepseek_v2_shapes: (5, 2), lfm2_shapes: (10, 1), qwen3_next_shapes: (8, 1),
                    glm_moe_dsa_shapes: (5, 2)}


@pytest.mark.parametrize("form", list(IN_PLACE))
def test_the_pool_is_carried_and_written_in_place(chip, form):
    """ISSUE 28's guard, since ISSUE 32 with the Pallas writer in the scatter's
    place.  ``forward_paged`` at Mistral's widths (three layers, the serving
    cells' pool of 368 blocks: one that fits vector memory the compiler
    prefetches there in slices, which no serving program sees) as a decode step
    ``[n, 1]``, a compacted and a padded chunk, and inside a two-step scan as
    the burst runs it; OLMoE's 16 KV heads and DeepSeek-V2's one latent leaf
    ``[5, 1024, 1, 128, 640]`` (its dense layer and four expert layers: two
    scans) as a decode step and a compacted chunk.  In the optimised program nothing
    has a pool-shaped result but the writer's custom call, every leaf aliased in
    and out: no ``copy``, ``dynamic-slice``, ``dynamic-update-slice`` or
    scatter of a layer or of the stack; each scan body calls the paged kernel
    once and the writer once; the program's temporaries are smaller than the
    pool.  LFM2 (ISSUE 33: two scans, of which the period's body alone holds an
    attention layer; heads of 64 packed two a 128-wide row, so no relayout) is
    held to the same, and its second cache with it: the conv layers' state,
    carried beside the pool, is written by scatters in place and never copied,
    sliced or updated whole.  Qwen3-Next (ISSUE 43: heads of 256, a state that
    is a TREE of two leaves, one of them float32 matrices) is held to the same
    for every leaf, in a decode step (the one-token update), a compacted chunk
    (the scan kernel, once a DeltaNet layer of the period) and a burst.  GLM-5
    (ISSUE 45: two pool leaves of unlike widths, index keys beside the latent,
    one of them scored and never attended) is held to the same for both leaves:
    one writer call and one paged kernel a scan body, no copy of either."""
    shapes, (n, t, bound), in_a_burst = IN_PLACE[form]
    layers, scans = LAYERS_AND_SCANS[shapes]
    module, cfg, params, kv = shapes(chip, layers=layers)
    leaves = jax.tree_util.tree_leaves(kv)

    def fwd(params, kv, tokens, n_tokens, start_pos, tables):
        return module.forward_paged(cfg, params, tokens, n_tokens, start_pos, tables, kv,
                                    block_size=128, live_token_bound=bound)

    state = kv.get("state") if isinstance(kv, dict) else None  # a row's slot: one more column
    ints = [chip(shape, jnp.int32) for shape in ((n, t), (n, ), (n, ), (n, 8 + (state is not None)))]
    compiled = jax.jit(burst_of(fwd) if in_a_burst else fwd,
                       donate_argnums=(1, )).lower(params, kv, *ints).compile()
    text = compiled.as_text()
    calls = kernel_calls(text)
    assert (calls["paged_attention"], calls["kv_write"]) == (scans, scans), calls
    results = pool_shaped_results(text, leaves[0].shape)
    assert [r[0] for r in results] == ["custom-call"] * scans, results  # the writer alone
    for leaf in jax.tree_util.tree_leaves(state):
        whole = pool_shaped_results(text, (1, ) + leaf.shape)  # the leaf whole, however folded
        assert whole and {r[0] for r in whole} <= {"scatter", "fusion"}, whole
    if shapes is qwen3_next_shapes:  # the scan kernel where a step has chunks, and only there
        assert calls.get("gdn_scan", 0) == (3 if t > 1 else 0), calls
    pool_bytes = sum(int(np.prod(leaf.shape)) * 2 for leaf in leaves)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


@pytest.mark.parametrize("n,t,bound,kernels,in_a_burst", [
    (32, 1, 512, ("ssd_update", ), False), (32, 256, 512, ("ssd_update", "ssd_scan"), False),
    (32, 1, None, ("ssd_update", ), True)], ids=["decode", "compacted", "burst"])
def test_a_state_leaf_by_reference_is_moved_by_its_kernels_alone(chip, n, t, bound, kernels,
                                                                 in_a_burst):
    """ISSUE 53: Granite's ``ssm`` leaf goes to ``mix`` by reference.  In the
    cell's decode step (32 rows), its compacted chunk pass (512 slots) and a
    burst's body inside its loop no operation produces the rows' matrices
    ``f32[32,128,64,128]`` (the slot read, the select for "begins" and the 134
    MB they cost a layer are gone) and none
    but the Mosaic kernels, each once a Mamba-2 scan body, produces the leaf's shape
    (no write back over the flat leaf ``f32[297,128,64,128]``, however folded);
    the leaf is aliased in and out and the program's temporaries are a fraction
    of it.  ISSUE 55: a chunk pass holds BOTH kernels a Mamba-2 layer (the update
    for its rows of one token, the scan, inside a loop of trips, for the rest:
    the leaf goes through that loop's carry uncopied) and its scan's layout is
    sized by the pass's tokens: ``ceil(512 / CHUNK) + WINDOW`` chunks, and
    nothing in the program has a dimension of the parent's ``ceil(512 / CHUNK)
    + n`` chunks or of their positions."""
    from deepspeed_tpu.ops.linear_attention import ssd
    module, cfg, params, kv = granite_shapes(chip, layers=10)
    ssm = kv["state"]["ssm"]

    def fwd(params, kv, tokens, n_tokens, start_pos, tables):
        return module.forward_paged(cfg, params, tokens, n_tokens, start_pos, tables, kv,
                                    block_size=128, live_token_bound=bound, last_rows=True)

    ints = [chip(shape, jnp.int32) for shape in ((n, t), (n, ), (n, ), (n, 20 + 1))]
    compiled = jax.jit(burst_of(fwd) if in_a_burst else fwd,
                       donate_argnums=(1, )).lower(params, kv, *ints).compile()
    text = compiled.as_text()
    calls = kernel_calls(text)
    assert {k: v for k, v in calls.items() if k.startswith("ssd_")} == dict.fromkeys(kernels, 2), calls
    assert calls["paged_attention"] == calls["kv_write"] == 1, calls
    rows = list((n, ) + ssm.shape[2:])
    assert [opcode for opcode, _, shapes in results(text) if rows in shapes] == []
    whole = pool_shaped_results(text, (1, ) + ssm.shape)
    assert [r[0] for r in whole] == ["custom-call"] * 2 * len(kernels), whole
    memory = compiled.memory_analysis()
    leaf_bytes = int(np.prod(ssm.shape)) * 4
    assert memory.alias_size_in_bytes >= leaf_bytes and memory.temp_size_in_bytes < leaf_bytes // 4
    if "ssd_scan" in kernels:
        chunks, by_rows = ssd.scan_chunks(n, t, bound), -(-bound // ssd.CHUNK) + n
        assert chunks == -(-bound // ssd.CHUNK) + ssd.WINDOW < by_rows
        laid = {dim for _, _, shapes in results(text) for dims in shapes for dim in dims}
        assert chunks * ssd.CHUNK in laid and not laid & {by_rows, by_rows * ssd.CHUNK}, laid


@pytest.mark.parametrize("n,t,bound,kernels,in_a_burst", [
    (64, 1, 1024, ("ssd_update", ), False), (64, 256, 1024, ("ssd_update", "ssd_scan"), False),
    (64, 1, None, ("ssd_update", ), True)], ids=["decode", "compacted", "burst"])
def test_a_layer_that_is_one_part_alone_holds_no_row_and_64_slots_are_moved_by_the_kernels_alone(
        chip, n, t, bound, kernels, in_a_burst):
    """ISSUE 62: Nemotron-3-Nano's step programs at the cell's size (9.17 GB of
    weights, 64 state slots).  The cache's leaves count their own kind of layer
    (``[6, 65, ...]`` state rows, ``[2, 1024, ...]`` pool rows for 14 layers: the six
    ``E`` layers hold a row in neither); in the decode step, the compacted chunk pass
    and a burst's body no operation produces the rows' matrices ``f32[64,64,64,128]``
    and none but the Mosaic kernels, each once an ``M`` layer of the scan's body,
    produces the flat leaf's shape; no operation copies the expert stack (``w_up``
    lies ``[.., 1856, 2688]`` and goes to ``gmm`` transposed); leaf and pool are
    aliased in and out and the program fits the chip beside them."""
    module, cfg, params, kv = nemotron_shapes(chip, layers=14)
    ssm, conv = kv["state"]["ssm"], kv["state"]["conv"]
    assert ssm.shape == (6, 65, 64, 64, 128) and conv.shape == (6, 65, 3, 6144)
    assert kv["k"].shape == kv["v"].shape == (2, 1024, 2, 128, 128) and kv["tally"].shape == (3, )

    def fwd(params, kv, tokens, n_tokens, start_pos, tables):
        return module.forward_paged(cfg, params, tokens, n_tokens, start_pos, tables, kv,
                                    block_size=128, live_token_bound=bound, last_rows=True)

    ints = [chip(shape, jnp.int32) for shape in ((n, t), (n, ), (n, ), (n, 8 + 1))]
    compiled = jax.jit(burst_of(fwd) if in_a_burst else fwd,
                       donate_argnums=(1, )).lower(params, kv, *ints).compile()
    text = compiled.as_text()
    calls = kernel_calls(text)
    # the period's body holds three M layers, one * layer and three E layers of two gmm each
    assert {k: v for k, v in calls.items() if k.startswith("ssd_")} == dict.fromkeys(kernels, 3), calls
    assert calls["paged_attention"] == calls["kv_write"] == 1 and calls["gmm"] == 6, calls
    rows = list((n, ) + ssm.shape[2:])
    assert [opcode for opcode, _, shapes in results(text) if rows in shapes] == []
    whole = pool_shaped_results(text, (1, ) + ssm.shape)
    assert [r[0] for r in whole] == ["custom-call"] * 3 * len(kernels), whole
    stack = list(params["experts"]["w_up"].shape)
    assert [opcode for opcode, _, shapes in results(text) if stack in shapes
            or [stack[0] * stack[1]] + stack[2:] in shapes] == [], "the expert stack is copied"
    memory = compiled.memory_analysis()
    leaf_bytes = int(np.prod(ssm.shape)) * 4
    # a burst's program lays the M layers' W_in [2, 2688, 10304] out anew once a call (10,304 = 80.5
    # lane tiles: 110 MB a position of the period, 0.33 GB); a step program takes them as they lie
    assert memory.alias_size_in_bytes >= leaf_bytes
    assert memory.temp_size_in_bytes < (leaf_bytes * 3 // 4 if in_a_burst else leaf_bytes // 8)
    held = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert held < 12.5e9, held  # weights 9.17 GB, state 0.83, pool 0.27 and the pass's temporaries


# the lowered text of two families that share ``paged_forward``'s mixer contract (and
# Qwen3-Next ``gated_delta.lay_on_chunk_edges``) with Granite, hashed at the parent of ISSUE 55
# and re-pinned in ISSUE 59, which changed that contract on purpose (``filtered`` for ``taps``)
PROGRAMS_BEFORE = {"qwen3_next_compacted": "3a21cec20775fc29", "qwen3_next_padded": "a3336a756d3dd3bd",
                   "lfm2_compacted": "b0c7b817dc68f0ed"}


@pytest.mark.parametrize("case", sorted(PROGRAMS_BEFORE))
def test_a_family_whose_state_goes_by_value_lowers_to_the_program_it_was(case):
    """ISSUE 55 changes Granite's scan and what a ``StateRef`` carries, and nothing a
    family whose leaves go by value traces: Qwen3-Next's chunk programs (the gated
    delta scan keeps a chunk a row: ``ceil(S / 64) + n``) and LFM2's lower to the
    text they lowered to before it."""
    import hashlib
    from deepspeed_tpu.models import lfm2, qwen3_next
    module, cfg, bound = {
        "qwen3_next_compacted": (qwen3_next, qwen3_next.Qwen3NextConfig.tiny(
            experts=16, local_experts=4), 32),
        "qwen3_next_padded": (qwen3_next, qwen3_next.Qwen3NextConfig.tiny(
            experts=16, local_experts=4), None),
        "lfm2_compacted": (lfm2, lfm2.Lfm2Config.tiny(), 32)}[case]
    params = jax.eval_shape(lambda: module.init_params(cfg, jax.random.PRNGKey(0)))
    kv = jax.eval_shape(lambda: module.init_paged_cache(cfg, 16, 8, dtype=jnp.float32, state_slots=5))
    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = jax.jit(lambda p, kv, tok, nt, sp, tab: module.forward_paged(
        cfg, p, tok, nt, sp, tab, kv, block_size=8, live_token_bound=bound, last_rows=True)).lower(
            params, kv, ints(4, 16), ints(4), ints(4), ints(4, 5)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PROGRAMS_BEFORE[case]


@pytest.mark.parametrize("n,t,bound,kernels,in_a_burst", [
    (16, 1024, 1024, ("kda_update", "kda_scan"), False), (16, 1, None, ("kda_update", ), True)],
    ids=["compacted", "burst"])
def test_a_latent_pool_and_a_state_by_reference_are_each_held_once(chip, n, t, bound, kernels,
                                                                  in_a_burst):
    """ISSUE 58: Ling-3.0's two caches in one program at the cell's size.  In its compacted
    chunk pass (1,024 slots) and a decode step as a burst's body inside its loop: the LATENT POOL is written by the writer kernel alone, once an MLA scan body, and
    attended by the paged kernel once (no pool-shaped result else: no copy, slice or update
    of it); the RECURRENT leaf goes by reference: no operation produces the rows' matrices
    ``f32[16,32,128,128]`` and none but the Mosaic kernels, each once a KDA scan body (three
    of the five scans hold KDA layers), produces the leaf's shape; both are aliased in and
    out, and the program's temporaries are under either (a burst's 0.31 GB are three
    weights laid out anew once a burst, outside its loop: ``W_f`` of the two KDA stacks,
    whose product leaves in float32, and the head)."""
    module, cfg, params, kv = ling_shapes(chip, layers=12)
    recurrent, latent = kv["state"]["recurrent"], kv["latent"]

    def fwd(params, kv, tokens, n_tokens, start_pos, tables):
        return module.forward_paged(cfg, params, tokens, n_tokens, start_pos, tables, kv,
                                    block_size=128, live_token_bound=bound, last_rows=True)

    ints = [chip(shape, jnp.int32) for shape in ((n, t), (n, ), (n, ), (n, 32 + 1))]
    compiled = jax.jit(burst_of(fwd) if in_a_burst else fwd,
                       donate_argnums=(1, )).lower(params, kv, *ints).compile()
    text = compiled.as_text()
    calls = kernel_calls(text)
    assert {k: v for k, v in calls.items() if k.startswith("kda_")} == dict.fromkeys(kernels, 3), calls
    assert calls["paged_attention"] == calls["kv_write"] == 2, calls  # the two MLA layers: a scan each
    assert [r[0] for r in pool_shaped_results(text, latent.shape)] == ["custom-call"] * 2
    rows = list((n, ) + recurrent.shape[2:])
    assert [opcode for opcode, _, shapes in results(text) if rows in shapes] == []
    whole = pool_shaped_results(text, (1, ) + recurrent.shape)
    assert [r[0] for r in whole] == ["custom-call"] * 3 * len(kernels), whole
    memory = compiled.memory_analysis()
    leaf_bytes, pool_bytes = int(np.prod(recurrent.shape)) * 4, int(np.prod(latent.shape)) * 2
    assert memory.alias_size_in_bytes >= leaf_bytes + pool_bytes
    assert memory.temp_size_in_bytes < min(leaf_bytes, pool_bytes)

