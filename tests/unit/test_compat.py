"""compat tests: every registered symbol resolves to the one spelling the
installed jax ships, the resolver's errors name their remedy, and the exports
run for real.  There is no second candidate, fallback or probe to test."""
# dslint: disable-file=direct-shimmed-import  # the shim's own tests reference the banned spellings by design

import importlib

import jax
import numpy as np
import pytest

from deepspeed_tpu import compat


@pytest.fixture(autouse=True)
def _fresh_resolution_cache():
    """Monkeypatched resolutions must not leak into later tests."""
    yield
    compat._cache.clear()


# ------------------------------------------------------------- resolution
class TestResolution:
    def test_every_registered_symbol_resolves_on_this_jax(self):
        for name in compat.SHIMMED_SYMBOLS:
            obj = compat.resolve_symbol(name, refresh=True)
            assert obj is not None
            assert compat.resolved_source(name) in compat.SHIMMED_SYMBOLS[name]

    def test_every_symbol_has_exactly_one_spelling_and_it_is_jax(self):
        # code for the one installation there is: no second candidate, and no
        # candidate inside this package (a reimplementation for another jax)
        for name, candidates in compat.SHIMMED_SYMBOLS.items():
            assert len(candidates) == 1, (name, candidates)
            assert candidates[0].startswith("jax"), (name, candidates)

    def test_exports_are_the_jax_objects_themselves(self):
        pltpu = importlib.import_module("jax.experimental.pallas.tpu")
        assert compat.shard_map is jax.shard_map
        assert compat.CompilerParams is pltpu.CompilerParams
        assert compat.axis_size is jax.lax.axis_size
        assert compat.Space is jax.memory.Space

    def test_nothing_for_another_jax_is_left(self):
        with pytest.raises(ImportError):
            importlib.import_module("deepspeed_tpu.compat._fallbacks")
        for gone in ("supports_partial_manual", "ensure_cpu_multiprocess_collectives"):
            assert not hasattr(compat, gone)

    def test_registered_spelling_is_what_resolves(self, monkeypatch):
        sentinel = object()
        monkeypatch.setattr(jax, "shard_map", sentinel)
        assert compat.resolve_symbol("shard_map", refresh=True) is sentinel
        assert compat.resolved_source("shard_map") == "jax:shard_map"

    def test_unknown_symbol_raises(self):
        with pytest.raises(compat.CompatResolutionError, match="not a shimmed"):
            compat.resolve_symbol("definitely_not_registered")

    def test_exhausted_candidates_raise_with_remedy(self, monkeypatch):
        monkeypatch.setitem(compat.SHIMMED_SYMBOLS, "ghost",
                            ("jax:no_such_attr", "no.such.module:thing"))
        with pytest.raises(compat.CompatResolutionError) as exc:
            compat.resolve_symbol("ghost", refresh=True)
        msg = str(exc.value)
        assert "no_such_attr" in msg and "SHIMMED_SYMBOLS" in msg

    def test_resolution_is_cached_until_refresh(self, monkeypatch):
        first = compat.resolve_symbol("shard_map", refresh=True)
        monkeypatch.setattr(jax, "shard_map", object(), raising=False)
        assert compat.resolve_symbol("shard_map") is first  # cached
        assert compat.resolve_symbol("shard_map", refresh=True) is not first


# ------------------------------------------------------------ the exports
class TestExports:
    def test_shard_map_runs_for_real_on_this_jax(self):
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:1]), ("data", ))
        fn = compat.shard_map(lambda x: x * 2, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False)
        np.testing.assert_array_equal(np.asarray(fn(jnp.arange(4.0))),
                                      [0.0, 2.0, 4.0, 6.0])


    def test_space_members_are_device_put_targets_inside_jit(self):
        import jax.numpy as jnp

        @jax.jit
        def round_trip(x):
            parked = jax.device_put(x, compat.Space.Host)
            return jax.device_put(parked, compat.Space.Device) + 1.0

        np.testing.assert_array_equal(np.asarray(round_trip(jnp.zeros(3))),
                                      [1.0, 1.0, 1.0])

    def test_compiler_params_constructs_with_dimension_semantics(self):
        p = compat.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
        assert tuple(p.dimension_semantics) == ("parallel", "arbitrary")
