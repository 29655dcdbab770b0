"""ZeRO-3 parameter NVMe swap (the ZeRO-Infinity param path).

Analog of the reference swap_tensor param machinery:
``AsyncPartitionedParameterSwapper`` (partitioned_param_swapper.py:36 —
per-param NVMe files, aligned pinned buffer pool, swap_in/swap_out with
async handles), ``AsyncTensorSwapper`` (async_swapper.py:19), and the
prefetch driven by the ZeRO-3 coordinator
(partitioned_param_coordinator.py:514 ``__prefetch_nvme_param_partitions``).

TPU-native shape: the engine's compiled ZeRO-3 path gathers per-layer params
inside one XLA program, which requires all shards resident in HBM.  When even
the shards don't fit (offload_param: nvme), the layer loop must leave the
compiled program: ``SwappedLayerTrainer`` streams one layer at a time —
NVMe -> host buffer (async, double-buffered) -> device -> compute -> drop —
with the backward pass re-fetching layers in reverse (ZeRO-Infinity
re-gathers params for backward rather than caching them).  Device memory is
bounded by ONE layer's params + activations of the micro-batch, regardless
of model depth.
"""

import os
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.aio import build_aio_handle
from ...utils.logging import log_dist


class AsyncPartitionedParameterSwapper:
    """NVMe backing store for named param groups with a reusable host
    buffer pool and async prefetch.

    Protocol per key: ``swap_out(key, arrays)`` persists; ``swap_in_async(key)``
    starts reads into pool buffers; ``wait_in(key)`` joins and returns the
    arrays (buffers on loan); ``release(key)`` returns buffers to the pool.
    ``buffer_count`` bounds host memory exactly like the reference's
    aio buffer pool (swap_tensor/utils.py:37 MIN_AIO_BYTES pools).
    """

    def __init__(self, nvme_path: str, buffer_count: int = 4, aio_threads: int = 4,
                 use_odirect: bool = True):
        # O_DIRECT by default, like the reference's libaio queues
        # (deepspeed_aio_common.cpp): page-cache writeback throttling caps
        # buffered writes at ~100 MB/s on typical cloud VMs while direct IO
        # sustains the device's ~800 MB/s; tmpfs and other O_DIRECT-refusing
        # filesystems fall back per-file inside the library.
        self.dir = os.path.join(nvme_path, "dstpu_param_swap")
        os.makedirs(self.dir, exist_ok=True)
        self.aio = build_aio_handle(aio_threads, use_odirect=use_odirect)
        self.buffer_count = buffer_count
        self._free: List[np.ndarray] = []
        self._allocated = 0
        self._buf_bytes = 0
        self._manifest: Dict[str, List[tuple]] = {}   # key -> [(shape, dtype), ...]
        self._inflight: Dict[str, List[tuple]] = {}   # key -> [(rid, buffer, shape, dtype)]
        self._loaned: Dict[str, List[np.ndarray]] = {}

    # ------------------------------------------------------------ buffers
    # Accounting invariant: _allocated == loaned + in-flight + len(_free); it
    # only passes buffer_count via the warned growth path, so host memory is
    # bounded at ~buffer_count * max-leaf-bytes (the reference's pinned pool
    # contract, swap_tensor/utils.py:37).
    def _take_buffer(self, nbytes: int) -> np.ndarray:
        self._buf_bytes = max(self._buf_bytes, nbytes)
        for i in range(len(self._free) - 1, -1, -1):  # pool may hold mixed sizes
            if self._free[i].nbytes >= nbytes:
                return self._free.pop(i)
        if self._allocated >= self.buffer_count and self._free:
            # replace an undersized free buffer instead of growing the pool
            self._free.sort(key=lambda b: b.nbytes)
            self._free.pop(0)
            self._allocated -= 1
        if self._allocated >= self.buffer_count:
            # working set exceeded the configured pool: grow with a warning
            # rather than deadlocking the layer stream (reference asserts)
            from ...utils.logging import logger
            logger.warning(f"param swap pool grew beyond buffer_count={self.buffer_count}; "
                           f"consider raising offload_param.buffer_count")
        self._allocated += 1
        return np.empty(self._buf_bytes, np.uint8)

    # ------------------------------------------------------------ file ops
    def _file(self, key: str, i: int) -> str:
        return os.path.join(self.dir, f"{key.replace('/', '_')}.{i}.bin")

    def swap_out(self, key: str, arrays: Sequence[np.ndarray], wait: bool = True):
        """Persist a param group (async unless ``wait``)."""
        rids = []
        manifest = []
        for i, a in enumerate(arrays):
            a = np.asarray(a)
            manifest.append((a.shape, a.dtype))
            rids.append(self.aio.pwrite(self._file(key, i), a))
        self._manifest[key] = manifest
        if wait:
            for r in rids:
                self.aio.wait(r)
        return rids

    def swap_in_async(self, key: str):
        """Begin reading a group into pool buffers (the prefetch step)."""
        if key in self._inflight or key in self._loaned:
            return  # already prefetched / resident
        entries = []
        for i, (shape, dtype) in enumerate(self._manifest[key]):
            nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
            buf = self._take_buffer(nbytes)
            view = buf[:nbytes].view(dtype).reshape(shape)
            rid = self.aio.pread(self._file(key, i), view)
            entries.append((rid, buf, view))
        self._inflight[key] = entries

    def wait_in(self, key: str) -> List[np.ndarray]:
        """Join the prefetch (issuing it now if it wasn't) and loan the arrays."""
        if key not in self._inflight and key not in self._loaned:
            self.swap_in_async(key)
        if key in self._inflight:
            views = []
            for rid, buf, view in self._inflight.pop(key):
                self.aio.wait(rid)
                views.append((buf, view))
            self._loaned[key] = views
        return [view for _, view in self._loaned[key]]

    def release(self, key: str):
        """Return a group's buffers to the pool (reference
        remove_partition_and_release_buffers)."""
        for buf, _view in self._loaned.pop(key, []):
            self._free.append(buf)

    def available_swap_in_buffers(self) -> int:
        return len(self._free)


class SwappedLayerTrainer:
    """Layer-streamed training with NVMe-resident params (ZeRO-Infinity).

    ``layer_fn(params_l, x) -> x`` over ``num_layers`` homogeneous layers whose
    params live on NVMe; ``head_fn(head_params, x, batch) -> loss`` stays
    resident (embeddings/head are the reference's persistent params —
    persistence_threshold analog).  Forward streams layers 0..L-1 saving each
    layer's INPUT on host; backward streams L-1..0 re-fetching params,
    recomputing the layer forward under ``jax.vjp``, and stepping that layer's
    AdamW immediately (fp32 master + moments also NVMe-resident via the
    optimizer swapper pattern) so no full gradient tree ever materializes.
    """

    def __init__(self, layer_fn: Callable, num_layers: int, head_fn: Callable,
                 swapper: AsyncPartitionedParameterSwapper,
                 lr: float = 1e-3, betas=(0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 0.0, compute_dtype=jnp.bfloat16,
                 stem_fn: Optional[Callable] = None,
                 optimizer_device: str = "nvme",
                 offload_activations: bool = False):
        """``stem_fn(stem_params, x) -> hidden`` is the optional trainable input
        transform (token embedding) ahead of the layer stack; its params stay
        DEVICE-resident like the head's, with a jitted AdamW (the reference
        keeps embeddings persistent via param_persistence_threshold).
        ``optimizer_device``: "nvme" streams Adam moments per layer alongside
        the params; "cpu" pins them in host RAM (the reference's
        offload_optimizer: cpu + offload_param: nvme combo — ZeRO-Infinity with
        moments one tier up, halving per-step disk traffic).
        ``offload_activations``: keep layer-input checkpoints on host instead of
        HBM (the reference's cpu_checkpointing; costs 2x activations over the
        host link per step — leave off unless HBM is the binding constraint)."""
        assert optimizer_device in ("nvme", "cpu")
        self.layer_fn = layer_fn
        self.num_layers = num_layers
        self.head_fn = head_fn
        self.stem_fn = stem_fn
        self.swapper = swapper
        self.compute_dtype = compute_dtype
        self._np_compute = np.dtype(compute_dtype)  # ml_dtypes-backed (bf16 ok)
        self.optimizer_device = optimizer_device
        self.offload_activations = offload_activations
        from ...ops.adam.cpu_adam import DeepSpeedCPUAdam
        self.opt = DeepSpeedCPUAdam(lr=lr, betas=betas, eps=eps, weight_decay=weight_decay)
        self._default_lr = lr
        self.step_count = 0
        self._layer_treedef = None
        self._cpu_m: Optional[List[List[np.ndarray]]] = None  # [layer][leaf]
        self._cpu_v: Optional[List[List[np.ndarray]]] = None
        self._fwd_jit = jax.jit(lambda p, x: self.layer_fn(p, x))
        # backward recompute, compiled: (params, x, cotangent) -> (dparams, dx)
        self._bwd_jit = jax.jit(lambda p, x, ct: jax.vjp(self.layer_fn, p, x)[1](ct))

        def cast16(tree):
            return jax.tree_util.tree_map(lambda a: a.astype(compute_dtype), tree)

        # head loss+grads, compiled: the fp32 master head lives ON DEVICE and
        # casts to compute dtype INSIDE the jit (mixed-precision grads come
        # back fp32), so the 2 x vocab x hidden head tensors never cross the
        # host<->device link (PCIe) per step
        self._head_jit = jax.jit(
            lambda h32, x, y: jax.value_and_grad(
                lambda hh, xx: self.head_fn(cast16(hh), xx, y), argnums=(0, 1))(h32, x))
        if stem_fn is not None:
            self._stem_jit = jax.jit(lambda sp32, x: stem_fn(cast16(sp32), x))
            self._stem_bwd_jit = jax.jit(
                lambda sp32, x, ct: jax.vjp(lambda sp: stem_fn(cast16(sp), x), sp32)[1](ct)[0])

        # device-resident AdamW for the persistent (head/stem) groups — same
        # decoupled-decay math as the host cpu_adam stepping the streamed layers
        b1, b2 = betas

        def persist_step(params, m, v, grads, lr_t, step_t):
            flat_p, tdef = jax.tree_util.tree_flatten(params)
            flat_m = jax.tree_util.tree_leaves(m)
            flat_v = jax.tree_util.tree_leaves(v)
            flat_g = jax.tree_util.tree_leaves(grads)
            new_p, new_m, new_v = [], [], []
            for p, mm, vv, g in zip(flat_p, flat_m, flat_v, flat_g):
                g = g.astype(jnp.float32)
                mm = b1 * mm + (1 - b1) * g
                vv = b2 * vv + (1 - b2) * g * g
                mhat = mm / (1 - jnp.power(b1, step_t))
                vhat = vv / (1 - jnp.power(b2, step_t))
                new_p.append(p - lr_t * (mhat / (jnp.sqrt(vhat) + eps) + weight_decay * p))
                new_m.append(mm)
                new_v.append(vv)
            unf = lambda leaves: jax.tree_util.tree_unflatten(tdef, leaves)
            return unf(new_p), unf(new_m), unf(new_v)

        self._persist_opt = jax.jit(persist_step, donate_argnums=(0, 1, 2))
        self._head_m = self._head_v = None
        self._stem_m = self._stem_v = None

    # ---------------------------------------------------------- initialize
    def init_from_stacked(self, stacked_params: Any, head_params: Any,
                          stem_params: Any = None):
        """Shard a [L, ...] stacked layer pytree onto NVMe (fp32 master +
        zero moments per layer) and keep head/stem params host-resident.
        One layer's worth of host copies at a time — broadcast-stacked or
        memmap'd leaves never materialize in full."""
        leaves, self._layer_treedef = jax.tree_util.tree_flatten(stacked_params)
        if self.optimizer_device == "cpu":
            self._cpu_m = [None] * self.num_layers
            self._cpu_v = [None] * self.num_layers
        for l in range(self.num_layers):
            layer = [np.asarray(leaf[l], np.float32) for leaf in leaves]
            rids = self.swapper.swap_out(self._pkey(l), layer, wait=False)
            if self.optimizer_device == "cpu":
                self._cpu_m[l] = [np.zeros_like(a) for a in layer]
                self._cpu_v[l] = [np.zeros_like(a) for a in layer]
            else:
                zeros = [np.zeros_like(a) for a in layer]
                rids += self.swapper.swap_out(self._mkey(l), zeros, wait=False)
                rids += self.swapper.swap_out(self._vkey(l), zeros, wait=False)
            # join per layer: unbounded in-flight writes would buffer every
            # layer's source arrays (they're host views into the stacked tree)
            for r in rids:
                self.swapper.aio.wait(r)
        # persistent groups: fp32 master ON DEVICE (uploaded once, not per step)
        self.head = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), head_params)
        self.stem = (None if stem_params is None else
                     jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), stem_params))
        n = sum(int(np.prod(np.shape(x))) for x in leaves)
        log_dist(f"param nvme swap: {self.num_layers} layers, {n/1e6:.2f}M stacked elems "
                 f"on {self.swapper.dir} (moments: {self.optimizer_device})", ranks=[0])

    def _pkey(self, l):
        return f"layer{l}.p"

    def _mkey(self, l):
        return f"layer{l}.m"

    def _vkey(self, l):
        return f"layer{l}.v"

    def _device_params(self, host_leaves):
        """Upload one layer in COMPUTE dtype: the fp32->bf16 cast runs on host
        so half the bytes cross the host->device link (PCIe), which halves
        the per-layer stream time."""
        tree = jax.tree_util.tree_unflatten(self._layer_treedef, host_leaves)
        # astype always copies (even same-dtype): the source is a POOLED buffer
        # that recycles as soon as we release it — an uploaded view would race
        # the async transfer
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a).astype(self._np_compute)), tree)

    def _zeros_like_tree(self, tree):
        return jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), tree)

    # ---------------------------------------------------------- train step
    def train_step(self, batch: Dict[str, np.ndarray], lr: Optional[float] = None):
        """One full fwd+bwd+update with layer streaming.  Returns the loss."""
        lr_f = float(lr) if lr is not None else self._default_lr  # dslint: disable=host-sync-in-hot-path  # lr arrives as a host scalar (engine._host_lr); this float() is a no-op coercion, not a device fetch
        if self.stem_fn is not None:
            x_tokens = jnp.asarray(batch["x"])
            x = self._stem_jit(self.stem, x_tokens)
        else:
            x = jnp.asarray(batch["x"], self.compute_dtype)
        saved_inputs: List = [None] * self.num_layers

        # ---- forward: stream 0..L-1, double-buffered prefetch
        self.swapper.swap_in_async(self._pkey(0))
        for l in range(self.num_layers):
            # wait FIRST so layer l's buffer is the one recycled; prefetch l+1
            # unconditionally — it overlaps this layer's compute, and gating on
            # free buffers made layer 1's read synchronous every step
            host = self.swapper.wait_in(self._pkey(l))
            if l + 1 < self.num_layers:
                self.swapper.swap_in_async(self._pkey(l + 1))
            # activation checkpoint: HBM by default (L x micro x seq x hidden
            # bf16 — ~0.5 GB at 7B/seq2048/micro1); host when requested
            saved_inputs[l] = np.asarray(x) if self.offload_activations else x  # dslint: disable=host-sync-in-hot-path  # opt-in cpu_checkpointing: offloading the activation to host RAM is the feature
            x = self._fwd_jit(self._device_params(host), x)
            self.swapper.release(self._pkey(l))

        # ---- head loss + grads; head master/moments stay on device
        (loss, dhead, dx) = self._head_grads(self.head, x, batch)
        self.step_count += 1
        step = self.step_count
        if self._head_m is None:
            self._head_m = self._zeros_like_tree(self.head)
            self._head_v = self._zeros_like_tree(self.head)
        self.head, self._head_m, self._head_v = self._persist_opt(
            self.head, self._head_m, self._head_v, dhead,
            jnp.float32(lr_f), jnp.int32(step))

        # ---- backward: stream L-1..0, recompute layer fwd, step immediately
        for l in reversed(range(self.num_layers)):
            host = self.swapper.wait_in(self._pkey(l))
            if self.optimizer_device == "nvme":
                # moments overlap this layer's recompute (prefetch now, join
                # after the bwd_jit below)
                self.swapper.swap_in_async(self._mkey(l))
                self.swapper.swap_in_async(self._vkey(l))
            if l - 1 >= 0:
                self.swapper.swap_in_async(self._pkey(l - 1))
            params_dev = self._device_params(host)
            x_in = jnp.asarray(saved_inputs[l], self.compute_dtype)
            dparams, dx = self._bwd_jit(params_dev, x_in, dx.astype(self.compute_dtype))
            # this layer's optimizer state: RAM-resident (cpu) or streamed (nvme)
            if self.optimizer_device == "cpu":
                m_host, v_host = self._cpu_m[l], self._cpu_v[l]
            else:
                m_host = self.swapper.wait_in(self._mkey(l))
                v_host = self.swapper.wait_in(self._vkey(l))
            grads = [np.asarray(g, np.float32) for g in jax.tree_util.tree_leaves(dparams)]  # dslint: disable=host-sync-in-hot-path  # ZeRO-Infinity by design: the host CPU-Adam steps each streamed layer, so its grads must land on host
            for p, m, v, g in zip(host, m_host, v_host, grads):
                self.opt.step(p.ravel(), m.ravel(), v.ravel(), g.ravel(), lr=lr_f, step=step)
            # join THIS layer's writes (by rid — wait_all would orphan the
            # in-flight prefetch of layer l-1) before its buffers recycle: a
            # pooled buffer must not be overwritten mid-write, and the next
            # step's forward re-reads these files
            rids = self.swapper.swap_out(self._pkey(l), host, wait=False)
            if self.optimizer_device == "nvme":
                rids += self.swapper.swap_out(self._mkey(l), m_host, wait=False)
                rids += self.swapper.swap_out(self._vkey(l), v_host, wait=False)
            for r in rids:
                self.swapper.aio.wait(r)
            self.swapper.release(self._pkey(l))
            if self.optimizer_device == "nvme":
                self.swapper.release(self._mkey(l))
                self.swapper.release(self._vkey(l))

        # ---- stem (embedding) grads from the dx that reached layer 0's input
        if self.stem_fn is not None:
            dstem = self._stem_bwd_jit(self.stem, x_tokens, dx.astype(self.compute_dtype))
            if self._stem_m is None:
                self._stem_m = self._zeros_like_tree(self.stem)
                self._stem_v = self._zeros_like_tree(self.stem)
            self.stem, self._stem_m, self._stem_v = self._persist_opt(
                self.stem, self._stem_m, self._stem_v, dstem,
                jnp.float32(lr_f), jnp.int32(step))
        return float(loss)  # dslint: disable=host-sync-in-hot-path  # the step's one deliberate sync: the backward walk above already joined, and callers (engine nvme path) need the host loss

    def _head_grads(self, head32, x, batch):
        loss, grads = self._head_jit(head32, x, jnp.asarray(batch["y"]))
        return loss, grads[0], grads[1]

    # ------------------------------------------------------------- export
    def gather_stacked_params(self):
        """Re-stack the NVMe-resident fp32 master params into the [L, ...]
        host pytree they were initialized from — the zero_to_fp32 analog for
        the streamed path (reference utils/zero_to_fp32.py consolidates
        partitioned masters the same way, one shard at a time)."""
        per_layer = []
        for l in range(self.num_layers):
            host = self.swapper.wait_in(self._pkey(l))
            per_layer.append([np.array(a, np.float32) for a in host])
            self.swapper.release(self._pkey(l))
        stacked = [np.stack([per_layer[l][i] for l in range(self.num_layers)])
                   for i in range(len(per_layer[0]))]
        return jax.tree_util.tree_unflatten(self._layer_treedef, stacked)

    # ---------------------------------------------------------- inference
    def forward(self, x: np.ndarray):
        if self.stem_fn is not None:
            x = self._stem_jit(self.stem, jnp.asarray(x))
        else:
            x = jnp.asarray(x, self.compute_dtype)
        self.swapper.swap_in_async(self._pkey(0))
        for l in range(self.num_layers):
            host = self.swapper.wait_in(self._pkey(l))
            if l + 1 < self.num_layers:
                self.swapper.swap_in_async(self._pkey(l + 1))
            x = self._fwd_jit(self._device_params(host), x)
            self.swapper.release(self._pkey(l))
        return x
