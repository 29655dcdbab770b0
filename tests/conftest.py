"""Test harness configuration.

Analog of the reference's distributed-test harness (tests/unit/common.py): the
reference launches N real ranks on one host; on TPU-native JAX we instead simulate
an 8-device mesh on CPU via XLA host-platform device partitioning — the pattern the
reference's accelerator-portable suite enables (tests/unit/common.py:111).

MUST run before any jax import, hence module-level env mutation in conftest.
"""

import os

# Tests run on the CPU backend whatever the machine holds: they check results
# at tiny sizes on a virtual 8-device mesh, and a chip belongs to one process at
# a time, which the test workers are not.  The environment is set before jax is
# imported; the config update below covers a jax that something imported first
# (backend init is lazy).
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    """Under xdist's ``--dist loadfile`` the files go to the workers in the order they were
    collected, not xdist's default of the most cases first.  By cases, a file of few and long
    ones comes last (``tests/chipbench/test_reference_longcat_flash.py``: 9 cases, 220-345 s,
    begun after 1,170 s of a run of 1,540), and one worker ends minutes after the other
    five.  Collected, ``tests/chipbench`` comes first (``test_harness.py``, the longest file,
    at second 0) and the run ends on ``tests/unit/test_*.py``, files of seconds.  The option
    is xdist's own ``--no-loadscope-reorder``; without xdist there is nothing to set."""
    if getattr(config.option, "loadscopereorder", False):
        config.option.loadscopereorder = False


LONG_FIRST = ("tests/chipbench/", "tests/unit/ops/", "tests/unit/inference/")


def pytest_collection_modifyitems(items):
    """The directories of long files first (a stable sort: a file's cases stay together and
    in their order), so that the last files a worker is handed are ``tests/unit/test_*.py``'s
    and not ``tests/unit/ops/test_tpu_compile*.py`` (200 s each, begun at second 1,140 of
    1,380 in collected order)."""
    items.sort(key=lambda item: next((i for i, d in enumerate(LONG_FIRST) if item.nodeid.startswith(d)),
                                     len(LONG_FIRST)))


@pytest.fixture(autouse=True)
def _reset_global_topology():
    yield
    from deepspeed_tpu.parallel import reset_topology
    reset_topology()
    from deepspeed_tpu.models.transformer import set_default_attention
    set_default_attention(None)


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """The Pallas kernels run interpreted, in place of their XLA reference math."""
    from deepspeed_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "INTERPRET", True)


@pytest.fixture
def mesh8():
    """An 8-device (data=8) topology."""
    from deepspeed_tpu.parallel import MeshTopology
    return MeshTopology.from_axis_dict({"data": 8})


@pytest.fixture
def mesh_2x4():
    from deepspeed_tpu.parallel import MeshTopology
    return MeshTopology.from_axis_dict({"data": 2, "tensor": 4})
