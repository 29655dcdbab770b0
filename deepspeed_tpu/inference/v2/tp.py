"""Tensor-parallel sharding for v2 (ragged/paged) serving.

Analog of the reference's v2 sharding-helper tree
(inference/v2/model_implementations/sharding/{qkv,mlp,attn,embedding,unembed}.py
+ the TP group the engine builds on, inference/v2/engine_v2.py:81-92): the
reference hand-slices each weight per TP rank at load time; here the model's
``tp_rules`` (or AutoTP path inference) produce a PartitionSpec tree, params and
the paged KV pool are placed with NamedShardings, and the ragged forward runs
under ``shard_map`` with ``tp_axis`` threading psums through the row-parallel
matmuls (a family's ``finish`` callable; models/transformer.py paged_forward).

Layout (matching the reference helpers):
  qkv (wq/wk/wv)      column-parallel — heads split over 'tensor'  (sharding/qkv.py)
  attn out (wo)       row-parallel    — psum                       (sharding/attn.py)
  mlp up/gate         column-parallel                              (sharding/mlp.py)
  mlp down            row-parallel    — psum
  embedding           replicated                                   (sharding/embedding.py)
  lm head             vocab-parallel  — all_gather of logit shards (sharding/unembed.py)
  paged KV pool       head-sharded    — dim 2 of [L, NB, KV, bs, Dh]
"""

from typing import Any, Callable, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ...parallel.mesh import TENSOR_AXIS, MeshTopology
from ...runtime.zero.sharding import _normalize_rule, _path_str
from ..auto_tp import auto_tp_rules


def resolve_rules(model_module, model_config=None) -> Callable:
    """Config-aware rules first (make_tp_rules(config) — models whose layout
    depends on head counts, e.g. falcon's MQA KV replication), then the static
    tp_rules, then AutoTP pattern inference."""
    maker = getattr(model_module, "make_tp_rules", None)
    if maker is not None and model_config is not None:
        return maker(model_config)
    return getattr(model_module, "tp_rules", None) or auto_tp_rules


def param_specs(model_module, params, tp: int, model_config=None):
    """PartitionSpec tree for v2 params over the 'tensor' axis.

    Raises when a rule names a dim not divisible by tp — silent replication
    there would serve wrong math under shard_map (local head counts are derived
    from the shard shapes)."""
    rules = resolve_rules(model_module, model_config)

    def spec_for(path, leaf):
        shape = np.shape(leaf)
        path_s = _path_str(path)
        dims = [None] * len(shape)
        for dim, axis in _normalize_rule(rules(path_s, tuple(shape))):
            if axis != TENSOR_AXIS:
                continue  # v2 serving shards over 'tensor' only
            if shape[dim] % tp != 0:
                raise ValueError(
                    f"v2 TP: param {path_s} dim {dim} ({shape[dim]}) not divisible by "
                    f"tp={tp}; pick a tp that divides heads/ffn/vocab")
            dims[dim] = TENSOR_AXIS
        return PartitionSpec(*dims)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def kv_pool_spec(kv_pool, tp: int = 0) -> Any:
    """Pool sharding: leaves are [L, NB, KV, bs, Dh] — head-shard dim 2 when it
    divides tp, else REPLICATE (MQA: every shard holds the single KV head and
    computes it identically; the reference's KV-replication fallback,
    sharding/qkv.py)."""
    def spec(leaf):
        kv_heads = np.shape(leaf)[2]
        if tp and kv_heads % tp != 0:
            return PartitionSpec()
        return PartitionSpec(None, None, TENSOR_AXIS)

    return jax.tree_util.tree_map(spec, kv_pool)


def validate_model(model_config, tp: int, model_module=None) -> None:
    """Head/GQA divisibility — the same constraint the reference asserts in its
    sharding helpers (sharding/attn.py head-distribution logic).  MQA (1 KV
    head) is allowed ONLY for models with config-aware ``make_tp_rules`` that
    keep wk/wv replicated (falcon) — static rule sets that unconditionally
    shard wk/wv would silently split the single head's feature dim."""
    h = getattr(model_config, "num_heads", None)
    kv = getattr(model_config, "num_kv_heads", h)
    if h is not None and h % tp != 0:
        raise ValueError(f"v2 TP: num_heads={h} not divisible by tp={tp}")
    mqa_ok = kv == 1 and model_module is not None and hasattr(model_module, "make_tp_rules")
    if kv is not None and kv % tp != 0 and not mqa_ok:
        raise ValueError(
            f"v2 TP: num_kv_heads={kv} not divisible by tp={tp} — partial KV-head "
            f"replication is not implemented; use tp <= num_kv_heads (MQA kv=1 "
            f"replicates fully for models with config-aware make_tp_rules, e.g. falcon)")


def place(topology: MeshTopology, tree, specs):
    """Place a pytree with NamedShardings from a PartitionSpec tree.

    Multi-controller meshes (TP spanning processes) can't eager-device_put to
    non-addressable devices — build from per-shard callbacks instead, each
    process materializing only its addressable shards (same pattern as
    checkpoint load, runtime/checkpointing.py)."""
    mesh = topology.mesh
    multi = jax.process_count() > 1

    def put(x, s):
        sharding = NamedSharding(mesh, s)
        if multi:
            host = np.asarray(x)  # dslint: disable=host-sync-in-hot-path  # init-time weight placement (multi-controller shard callback), not a serve-loop step-result fetch
            return jax.make_array_from_callback(host.shape, sharding,
                                                lambda idx, a=host: a[idx])
        return jax.device_put(x, sharding)

    return jax.tree_util.tree_map(put, tree, specs)
