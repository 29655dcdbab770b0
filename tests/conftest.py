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
