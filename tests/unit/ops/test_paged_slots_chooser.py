"""Beside the paged kernel's parity (``test_paged_slots.py``): the tile chooser's
``slots`` beside the parent's heads and rows, the counters that say how many
slots a step took, and the kernel's traced size (every program of a cell traces
and lowers it once: warm ``setup_s``)."""

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.attention import paged

from .test_paged_slots import BS


PARENTS_TILES = [  # (t, hq, kvh, dh, dv, (kvg, rows, splits, tile) at 7e19018, slots)
    (1, 32, 8, 128, None, (8, 16, 1, 16), 4), (9, 32, 8, 128, None, (8, 48, 1, 48), 4),
    (128, 32, 8, 128, None, (8, 512, 1, 256), 4), (256, 32, 8, 128, None, (8, 1024, 1, 256), 4),
    (512, 32, 8, 128, None, (4, 2048, 1, 256), 4), (1, 16, 16, 128, None, (16, 16, 1, 16), 4),
    (256, 16, 16, 128, None, (16, 256, 1, 256), 2), (1, 128, 1, 640, 512, (1, 128, 1, 128), 4),
    (16, 128, 1, 640, 512, (1, 2048, 1, 256), 4), (512, 128, 1, 640, 512, (1, 4096, 16, 256), 4),
    (1, 32, 4, 128, None, (4, 16, 1, 16), 4), (512, 32, 4, 128, None, (2, 4096, 1, 256), 4),
    (512, 64, 8, 128, None, (2, 4096, 1, 256), 4), (128, 71, 1, 64, None, (1, 9216, 1, 256), 4),
    (4096, 64, 8, 128, None, (1, 11008, 3, 256), 4), (512, 16, 16, 128, None, (16, 512, 1, 256), 2),
    (512, 64, 64, 128, None, (16, 512, 1, 256), 2), (1024, 32, 32, 128, None, (8, 1024, 1, 256), 4),
]


@pytest.mark.parametrize("t,hq,kvh,dh,dv,parents,slots", PARENTS_TILES, ids=lambda v: str(v))
def test_the_chooser_adds_slots_and_leaves_heads_and_rows_as_they_were(t, hq, kvh, dh, dv, parents,
                                                                       slots):
    """``slots`` never costs KV heads a step (PR 30's gain rests on them): the
    parent's ``(kvg, rows, splits, tile)`` at the cells' shapes (Mistral, OLMoE,
    DeepSeek-V2's latent pool, LFM2's packed heads) and at the widest the
    compile tests pin, with the most slots that reckon under ``VMEM_SLOTS_BYTES``."""
    got = paged.step_tile(t, hq, kvh, dh, 128, jnp.bfloat16, jnp.bfloat16, dv)
    assert got == parents + (slots, )
    kvg, rows, _, tile, _ = got
    need = {s: paged._step_vmem_bytes(kvg, rows, tile, dh, 128, 2, 2, dv, s) for s in (1, 2, 4)}
    assert need[1] <= paged.VMEM_BUDGET_BYTES and need[1] < need[2] < need[4]
    assert slots == 1 or need[slots] <= paged.VMEM_SLOTS_BYTES
    assert slots == 4 or need[2 * slots] > paged.VMEM_SLOTS_BYTES  # the wider step would not fit


def test_a_steps_reckoning_counts_the_wider_tiles():
    """Four slots: K and V tiles four times as large (two of each), and the
    scores, probabilities and masks of a row tile four times as wide."""
    one, four = (paged._step_vmem_bytes(8, 1024, 256, 128, 128, 2, 2, None, s) for s in (1, 4))
    tiles = 2 * 2 * 8 * 128 * 128 * 2
    work = 8 * 256 * 4 * 128 * 4
    assert four - one == 3 * tiles + 3 * work
    latent = [paged._step_vmem_bytes(1, 4096, 256, 640, 128, 2, 2, 512, s) for s in (1, 4)]
    assert latent[1] - latent[0] == 3 * (2 * 128 * 640 * 2) + 3 * (256 * 4 * 128 * 4)


# ------------------------------------------------------------ the traced size
def every_equation(jaxpr):
    """The equations of a jaxpr and of every jaxpr its equations hold (branches,
    loop bodies, the calls jnp makes of its own jitted helpers)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from every_equation(inner)


def count_equations(jaxpr) -> int:
    return sum(1 for _ in every_equation(jaxpr))


def kernel_equations(monkeypatch, n, t, hq, kvh, dh, maxb, dv=None, window=4096):
    """The kernel's body and its index maps, as ``paged_attention`` traces them."""
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    shape = jax.ShapeDtypeStruct
    ints = [shape(s, jnp.int32) for s in ((n, maxb), (n, ), (n, ), (n, ))]
    q, pool = shape((n, t, hq, dh), jnp.bfloat16), shape((256, kvh, 128, dh), jnp.bfloat16)
    if dv is None:
        traced = jax.make_jaxpr(lambda q, k, v, *i: paged.paged_attention(
            q, k, v, *i, block_size=128, window=window))(q, pool, pool, *ints)
    else:
        traced = jax.make_jaxpr(lambda q, k, *i: paged.paged_attention(
            q, k, None, *i, block_size=128, softmax_scale=0.1147, value_dim=dv))(q, pool, *ints)
    (call, ) = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    maps = sum(count_equations(m.index_map_jaxpr.jaxpr) for m in call.params["grid_mapping"].block_mappings)
    return count_equations(call.params["jaxpr"]) + maps


# The parent's counts, read with ``count_equations`` at commit 7e19018 (one table
# slot a step: body 118 / 240 / 230, its two K/V index maps 17 each).
PARENTS_EQUATIONS = {"mistral-n32-T1": 152, "mistral-n32-T256": 274, "mla-n4-T512": 247}
SHAPES = {"mistral-n32-T1": (32, 1, 32, 8, 128, 20), "mistral-n32-T256": (32, 256, 32, 8, 128, 20),
          "mla-n4-T512": (4, 512, 128, 1, 640, 64, 512, None)}


@pytest.mark.parametrize("program", sorted(SHAPES))
def test_the_kernels_traced_size_is_held(monkeypatch, program):
    """A cell meets 38-70 programs and each traces and lowers the kernel once:
    what the body and its index maps cost there is warm ``setup_s`` (PR 30's
    first form and PR 34 were refused by it).  The count does not grow with
    the table's width, so not with ``slots``, and stays within a quarter of
    the parent's.  A proxy: the measured trace-and-lower time decides
    (CHANGES.md, PR 35: a jnp operator costs five times a ``lax`` primitive to
    trace, and a BlockSpec costs more than all of this body's equations)."""
    n, t, hq, kvh, dh, maxb, *rest = SHAPES[program]
    counts = {b: kernel_equations(monkeypatch, n, t, hq, kvh, dh, b, *rest) for b in (4, 20, 40, maxb)}
    assert len(set(counts.values())) == 1, counts
    assert counts[maxb] <= 1.25 * PARENTS_EQUATIONS[program], (counts, PARENTS_EQUATIONS[program])


# ------------------------------------------------------------ the counter
def test_kernel_steps_count_the_grids_table_axis():
    """``n x ceil(b / slots) x passes`` beside ``table_slots``, with the slots
    of the program's ``t``; host integers."""
    from deepspeed_tpu.inference.v2.fastpath import ServeCounters
    counters = ServeCounters(kernel_slots={1: 4, 256: 2}.__getitem__)
    counters.count_slots(32, 1, 20, live_tokens=32, live_blocks=90)
    counters.count_slots(32, 256, 18, live_tokens=256, live_blocks=90, flat=256)
    counters.count_slots(16, 1, 10, live_tokens=160, live_blocks=40, passes=10)
    assert counters.table_slots == 32 * 20 + 32 * 18 + 16 * 10 * 10
    assert counters.kernel_steps == 32 * 5 + 32 * 9 + 16 * 3 * 10
    assert ServeCounters().kernel_slots(7) == 1 and "kernel_steps" in counters.snapshot()


@pytest.mark.parametrize("launch,rows", [
    (dict(n=32, t=1, b=20, live_tokens=32, live_blocks=90), 32),
    (dict(n=32, t=256, b=18, live_tokens=256, live_blocks=90, flat=256), 32),
    (dict(n=32, t=256, b=18, live_tokens=256, live_blocks=90), 32),
    (dict(n=16, t=1, b=10, live_tokens=160, live_blocks=40, passes=10), 160),
    (dict(n=8, t=5, b=10, live_tokens=24, live_blocks=40, every_position=True), 40),
], ids=["decode-step", "compacted-chunk", "padded-chunk", "burst-of-ten", "spec-verify"])
def test_head_rows_count_a_last_row_a_sequence_a_pass(launch, rows):
    """``head_rows``: n a forward pass of a step or a burst whatever the bucket's
    ``t`` or its flat slots (ISSUE 44: the head runs over each row's last live
    token alone), every slot of a program that scores every position."""
    from deepspeed_tpu.inference.v2.fastpath import ServeCounters
    counters = ServeCounters()
    counters.count_slots(**launch)
    assert counters.head_rows == counters.snapshot()["head_rows"] == rows
    assert counters.head_rows <= counters.token_slots


@pytest.mark.parametrize("group,align", [(4, 4), (1, 16), (8, 2), (128, 1), (71, 16), (6, 8)])
def test_attention_slots_count_the_layout_the_kernel_was_handed(group, align):
    """``attn_token_slots``: n x t a padded pass and every pass of a burst, the
    flat row axis over ``group`` a compacted one: its S slots and, a sequence,
    the positions that begin it on a whole sublane tile of rows."""
    from deepspeed_tpu.inference.v2.fastpath import ServeCounters
    assert paged.flat_token_slots(32, 256, group) == 256 + 32 * (align - 1)
    counters = ServeCounters(attn_slots=lambda n, flat: paged.flat_token_slots(n, flat, group))
    counters.count_slots(32, 1, 20, live_tokens=32, live_blocks=90)  # padded
    assert (counters.attn_token_slots, counters.token_slots) == (32, 32)
    counters.count_slots(32, 256, 18, live_tokens=256, live_blocks=90, flat=256)  # flat
    assert counters.token_slots == 32 + 256
    assert counters.attn_token_slots == 32 + 256 + 32 * (align - 1)
    counters.count_slots(16, 1, 10, live_tokens=160, live_blocks=40, passes=10)  # a burst of ten
    assert counters.attn_token_slots == 32 + 256 + 32 * (align - 1) + 160
    assert counters.snapshot()["attn_token_slots"] == counters.attn_token_slots
    plain = ServeCounters()  # no kernel's word on it: the flat slots themselves
    plain.count_slots(32, 256, 18, live_tokens=256, live_blocks=90, flat=256)
    assert plain.attn_token_slots == 256


def _grid_of_the_kernel(module, config, n, t, b, stateful=False):
    """The grid of the ``paged_attention`` call in the family's traced forward."""
    kv = module.init_paged_cache(config, 8, BS, dtype=jnp.float32,
                                 **({"state_slots": n} if stateful else {}))
    params = jax.eval_shape(lambda: module.init_params(config, jax.random.PRNGKey(0)))
    ints = [jax.ShapeDtypeStruct(s, jnp.int32) for s in ((n, t), (n, ), (n, ), (n, b + stateful))]
    traced = jax.make_jaxpr(lambda p, kv, *i: module.forward_paged(
        config, p, *i, kv, block_size=BS))(params, kv, *ints)

    grids = {eqn.params["grid_mapping"].grid for eqn in every_equation(traced.jaxpr)
             if eqn.primitive.name == "pallas_call" and eqn.params["name"] == "paged_attention"}
    return kv, grids


@pytest.mark.parametrize("family,t,b", [("llama", 1, 6), ("llama", 16, 5), ("deepseek_v2", 1, 7),
                                        ("deepseek_v2", 16, 4), ("lfm2", 1, 6), ("lfm2", 16, 3)])
def test_the_engines_slots_are_the_launched_programs(monkeypatch, family, t, b):
    """``transformer.paged_step_slots`` works the kernel's slots out of the
    family's config and pool; the grid of the program the family traces has
    ``ceil(b / slots)`` steps along the table: K and V pools, a latent pool with
    its ``paged_value_dim``, packed heads beside a state column in the table."""
    import importlib

    from deepspeed_tpu.models.transformer import paged_step_slots
    monkeypatch.setattr(paged, "_use_pallas", lambda: True)
    module = importlib.import_module(f"deepspeed_tpu.models.{family}")
    config = {"llama": lambda: module.LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, kv_heads=2),
              "deepseek_v2": lambda: module.DeepseekV2Config.tiny(local_experts=4),
              "lfm2": lambda: module.Lfm2Config.tiny()}[family]()
    kv, grids = _grid_of_the_kernel(module, config, 4, t, b, stateful=family == "lfm2")
    slots = paged_step_slots(module, config, kv, jnp.float32)[0](t)
    assert slots in paged.STEP_SLOTS and grids and {g[-1] for g in grids} == {-(-b // slots)}
