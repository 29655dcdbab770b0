"""``test_paged_slots_flat.py``'s check of ``q`` on the flat token axis, over the
head groupings, the masks, the value inside the key, a row split and bfloat16
(a file of its own: every ``kernel`` case is three interpreted kernels)."""

import jax.numpy as jnp
import pytest

from .test_paged_slots_flat import CHUNK, flat_is_the_padded_bucket


def _flat_cases():
    yield "gqa-32q8kv", dict(rows=CHUNK, t=256, hq=32, kvh=8, maxb=20), {}
    yield "mha-16q16kv", dict(rows=[(30, 5), (18, 1), (70, 16), (3, 3)], t=16, hq=16, kvh=16, maxb=6), {}
    yield "packed-64-wide-heads-group8", dict(rows=[(30, 5), (18, 1), (70, 16)], t=16, hq=16, kvh=2,
                                              maxb=6, dk=128), {}
    yield "falcon-71q1kv", dict(rows=[(30, 5), (18, 1), (70, 9)], t=16, hq=71, kvh=1, maxb=6), {}
    yield "window-40", dict(rows=[(150, 14), (90, 1), (64, 16)], t=16, hq=4, kvh=2, maxb=12), dict(window=40)
    yield "alibi", dict(rows=[(30, 5), (18, 1), (70, 16)], t=16, hq=8, kvh=2, maxb=6), dict(alibi=True)
    yield "latent-576-512", dict(rows=[(30, 5), (18, 1), (70, 16)], t=16, hq=8, kvh=1, maxb=6, dk=576,
                                 dv=512), dict(dv=512, scale=0.07)
    # a row split: one KV head's 2,048 rows in four grid steps of 512 (the budget cut to force it)
    yield "value-dim-rows-split-in-four", dict(rows=[(100, 40), (18, 1), (64, 64), (70, 3)], t=64, hq=32,
                                               kvh=1, maxb=8, dk=64, dv=32), dict(dv=32, scale=0.1, splits=4)
    yield "bf16-32q8kv", dict(rows=CHUNK, t=256, hq=32, kvh=8, maxb=20, dtype=jnp.bfloat16), {}


@pytest.mark.parametrize("path", ["kernel", "fallback"])
@pytest.mark.parametrize("name,case,how", list(_flat_cases()), ids=lambda v: v if isinstance(v, str) else "")
def test_q_on_the_flat_axis_is_the_padded_bucket_on_every_live_row(monkeypatch, name, case, how, path):
    flat_is_the_padded_bucket(monkeypatch, case, how, path)
