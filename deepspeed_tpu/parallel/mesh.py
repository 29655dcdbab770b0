"""Device mesh & parallelism topology.

TPU-native replacement for the reference's process-group factories
(deepspeed/utils/groups.py: ``_create_model_parallel:64``,
``_create_expert_and_data_parallel:113``, sequence accessors ``:452-491``) and
``ProcessTopology`` (deepspeed/runtime/pipe/topology.py:12).

Instead of creating torch.distributed process groups per parallelism flavor, we
construct ONE ``jax.sharding.Mesh`` with named axes; each reference "group" becomes
a mesh axis (or tuple of axes) that collectives reduce over:

  reference group                     mesh axis
  ------------------------------      -------------------
  data_parallel_group                 ("data",) (+ "fsdp" when ZeRO shards there)
  model_parallel_group (TP)           ("tensor",)
  pipe_parallel_group                 ("pipe",)
  expert_parallel_group               ("expert",)
  sequence_parallel_group             ("sequence",)
  sequence_data_parallel_group        ("data", "sequence")
  expert_data_parallel_group          ("data",) complement of expert
  zero hpZ secondary partition        inner slice of "fsdp" (ici-adjacent)

Axis order places "tensor"/"sequence" innermost so their collectives ride
ICI-adjacent links, and "pipe" outermost (DCN-friendly) — the same intent as the
reference's D+E vs E+D group layouts (blogs/comm-opt/README.md:37).
"""

import collections
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..runtime.config import MeshConfig
from ..utils.logging import logger

# Canonical axis names (every subsystem refers to these).
DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
TENSOR_AXIS = "tensor"
SEQUENCE_AXIS = "sequence"
EXPERT_AXIS = "expert"
PIPE_AXIS = "pipe"

ALL_AXES = (PIPE_AXIS, DATA_AXIS, FSDP_AXIS, EXPERT_AXIS, SEQUENCE_AXIS, TENSOR_AXIS)


class MeshTopology:
    """Named-axis cartesian device grid — analog of ``ProcessTopology``
    (runtime/pipe/topology.py:12) + ``PipelineParallelGrid`` (:251), realized as a
    ``jax.sharding.Mesh`` plus accessors mirroring groups.py."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    # ---- construction -------------------------------------------------------
    @classmethod
    def build(cls, config: Optional[MeshConfig] = None, devices: Optional[Sequence] = None) -> "MeshTopology":
        config = config or MeshConfig()
        devices = list(devices) if devices is not None else list(jax.devices())
        n = len(devices)
        sizes = dict(config.axis_sizes())
        fixed = 1
        wild_axis = None
        for a, s in sizes.items():
            if s == -1:
                wild_axis = a
            else:
                fixed *= s
        if wild_axis is None:
            if fixed != n:
                raise ValueError(f"mesh axes {sizes} multiply to {fixed} but {n} devices are present")
        else:
            if n % fixed != 0:
                raise ValueError(f"{n} devices not divisible by fixed axes product {fixed}")
            sizes[wild_axis] = n // fixed
        order = list(config.axis_order)
        for a in ALL_AXES:
            if a not in order:
                order.append(a)
        shape = [sizes[a] for a in order]
        grid = np.asarray(devices).reshape(shape)
        mesh = Mesh(grid, axis_names=tuple(order))
        logger.info(f"MeshTopology: {dict(zip(order, shape))} over {n} devices")
        return cls(mesh)

    @classmethod
    def from_axis_dict(cls, axes: Dict[str, int], devices: Optional[Sequence] = None) -> "MeshTopology":
        cfg = {a: axes.get(a, 1) for a in ALL_AXES}
        return cls.build(MeshConfig(**cfg), devices=devices)

    # ---- accessors (groups.py parity) ---------------------------------------
    def axis_size(self, axis: str) -> int:
        return self.mesh.shape[axis]

    @property
    def world_size(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    def get_data_parallel_world_size(self) -> int:
        """ZeRO's dp world: data × fsdp (the reference shards ZeRO state across the
        whole dp group; we split it into replicated 'data' and sharded 'fsdp')."""
        return self.axis_size(DATA_AXIS) * self.axis_size(FSDP_AXIS)

    def get_model_parallel_world_size(self) -> int:
        return self.axis_size(TENSOR_AXIS)

    def get_pipe_parallel_world_size(self) -> int:
        return self.axis_size(PIPE_AXIS)

    def get_expert_parallel_world_size(self) -> int:
        return self.axis_size(EXPERT_AXIS)

    def get_sequence_parallel_world_size(self) -> int:
        return self.axis_size(SEQUENCE_AXIS)

    def get_sequence_data_parallel_world_size(self) -> int:
        """Reference ``_get_sequence_data_parallel_world_size`` (groups.py:497):
        the group ZeRO shards across when Ulysses is active."""
        return self.get_data_parallel_world_size() * self.get_sequence_parallel_world_size()

    # Axis tuples for collectives (feed to lax.p* axis_name=...)
    def data_parallel_axes(self) -> Tuple[str, ...]:
        axes = tuple(a for a in (DATA_AXIS, FSDP_AXIS) if self.axis_size(a) > 1)
        return axes or (DATA_AXIS, )

    def sharding(self, spec: PartitionSpec) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def __enter__(self):
        self._ctx = self.mesh
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)

    def __repr__(self):
        return f"MeshTopology({dict(self.mesh.shape)})"


_GLOBAL_TOPOLOGY: Optional[MeshTopology] = None


def set_topology(topo: MeshTopology):
    global _GLOBAL_TOPOLOGY
    _GLOBAL_TOPOLOGY = topo


def get_topology() -> MeshTopology:
    global _GLOBAL_TOPOLOGY
    if _GLOBAL_TOPOLOGY is None:
        _GLOBAL_TOPOLOGY = MeshTopology.build()
    return _GLOBAL_TOPOLOGY


def peek_topology() -> Optional[MeshTopology]:
    """The topology an engine (or ``set_topology``) installed, or None — never
    builds one, so asking does not claim every device of the host."""
    return _GLOBAL_TOPOLOGY


def reset_topology():
    global _GLOBAL_TOPOLOGY
    _GLOBAL_TOPOLOGY = None
