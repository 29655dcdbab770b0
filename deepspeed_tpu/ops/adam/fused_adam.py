"""Pallas fused AdamW over flat parameter buffers.

TPU-native analog of the reference's multi-tensor-apply FusedAdam
(csrc/adam/multi_tensor_adam.cu, deepspeed/ops/adam/fused_adam.py): instead of
a multi-tensor CUDA launch, the optimizer state lives as ONE flat fp32 buffer
per (param/m/v) — the same flattening ZeRO does anyway — and a single grid
sweep updates p/m/v in place (input_output_aliases) with all elementwise math
fused in VMEM, one HBM read + write per buffer.

The engine uses this through ``fused_adamw_flat``; off-TPU the identical math
runs as plain XLA (which fuses it just as well on CPU — the kernel's win is
guaranteed aliasing + no small-op overhead on real chips).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._pallas import use_pallas as _use_pallas
from .. import _pallas

_BLOCK = 1 << 16  # elements per grid step (fp32: 256KB/buffer in VMEM)


def _flat_kernel_call(kernel, scal, arrays, n_out):
    """Run an elementwise flat-buffer kernel over (rows, 128) tiles.

    The first ``n_out`` arrays alias their outputs in place.  Returns the
    updated buffers, un-padded back to the original length.
    """
    n = arrays[0].shape[0]
    rows = max(8, min(_BLOCK // 128, int(np.ceil(n / 128))))
    chunk = rows * 128
    n_pad = int(np.ceil(n / chunk)) * chunk
    as2d = lambda x: jnp.pad(x, (0, n_pad - n)).reshape(n_pad // 128, 128)
    spec = pl.BlockSpec((rows, 128), lambda i: (i, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(n_pad // chunk, ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [spec] * len(arrays),
        out_specs=[spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((n_pad // 128, 128), jnp.float32)] * n_out,
        input_output_aliases={i + 1: i for i in range(n_out)},
        interpret=_pallas.INTERPRET,
        name=f"fused_{kernel.__name__.strip('_')}",
    )(scal, *[as2d(a) for a in arrays])
    return tuple(o.reshape(n_pad)[:n] for o in outs)


def _adamw_kernel(scal_ref, p_ref, m_ref, v_ref, g_ref, po_ref, mo_ref, vo_ref):
    lr = scal_ref[0]
    beta1, beta2, eps, wd, bc1, bc2 = (scal_ref[1], scal_ref[2], scal_ref[3],
                                       scal_ref[4], scal_ref[5], scal_ref[6])
    g = g_ref[:].astype(jnp.float32)
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * g * g
    m_hat = m / bc1
    v_hat = v / bc2
    update = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p_ref[:]
    po_ref[:] = p_ref[:] - lr * update
    mo_ref[:] = m
    vo_ref[:] = v


def fused_adamw_flat(p, m, v, g, *, lr, beta1=0.9, beta2=0.999, eps=1e-8,
                     weight_decay=0.0, step=1):
    """One AdamW step on flat fp32 buffers p/m/v with (possibly bf16) grad g.

    Returns (p_new, m_new, v_new).  ``step`` is 1-based; bias correction is
    computed host-side when static, traced otherwise.
    """
    step = jnp.asarray(step, jnp.float32)
    bc1 = 1.0 - jnp.power(jnp.asarray(beta1, jnp.float32), step)
    bc2 = 1.0 - jnp.power(jnp.asarray(beta2, jnp.float32), step)
    scal = jnp.stack([jnp.asarray(x, jnp.float32) for x in
                      (lr, beta1, beta2, eps, weight_decay)] + [bc1, bc2])
    if not _use_pallas():
        gf = g.astype(jnp.float32)
        m_new = beta1 * m + (1 - beta1) * gf
        v_new = beta2 * v + (1 - beta2) * gf * gf
        update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps) + weight_decay * p
        return p - scal[0] * update, m_new, v_new

    return _flat_kernel_call(_adamw_kernel, scal, (p, m, v, g), n_out=3)


def _lion_kernel(scal_ref, p_ref, m_ref, g_ref, po_ref, mo_ref):
    lr, beta1, beta2, wd = scal_ref[0], scal_ref[1], scal_ref[2], scal_ref[3]
    g = g_ref[:].astype(jnp.float32)
    c = beta1 * m_ref[:] + (1.0 - beta1) * g
    po_ref[:] = p_ref[:] - lr * (jnp.sign(c) + wd * p_ref[:])
    mo_ref[:] = beta2 * m_ref[:] + (1.0 - beta2) * g


def fused_lion_flat(p, m, g, *, lr, beta1=0.9, beta2=0.99, weight_decay=0.0):
    """Lion step on flat buffers (reference csrc/lion/ analog)."""
    scal = jnp.stack([jnp.asarray(x, jnp.float32) for x in (lr, beta1, beta2, weight_decay)])
    if not _use_pallas():
        gf = g.astype(jnp.float32)
        c = beta1 * m + (1 - beta1) * gf
        return p - scal[0] * (jnp.sign(c) + weight_decay * p), beta2 * m + (1 - beta2) * gf
    return _flat_kernel_call(_lion_kernel, scal, (p, m, g), n_out=2)
