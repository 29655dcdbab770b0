"""The chip's compiler, asked without a chip: what ``test_tpu_compile.py`` and
``test_tpu_compile_programs.py`` share."""

import jax
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def _cache_off():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(one_chip, _cache_off, monkeypatch):
    """``shape, dtype -> ShapeDtypeStruct`` on the described chip, with the
    kernels' dispatch steered to Pallas: ``use_pallas()`` asks
    ``jax.default_backend()``, which still sees the CPU here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
