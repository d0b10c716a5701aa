"""jit'd public wrappers around the Pallas kernels: padding to block/lane
alignment, granularity dispatch, and the quantize->int8-matmul->dequant
composite that realizes the paper's W8A8 recipe with real integer compute.

``interpret=None`` auto-selects: compiled on TPU, interpret mode on CPU
(functional validation; the kernels TARGET TPU).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.qconfig import Granularity, QuantSpec, RoundMode
from repro.core.quantizer import quantize_int
from repro.kernels import int8_matmul as _mm
from repro.kernels import resolve_interpret
from repro.kernels import qdq as _qdq

_EPS = 1e-12


def _pad_to(x: jnp.ndarray, mult_r: int, mult_c: int) -> jnp.ndarray:
    r, c = x.shape
    pr, pc = (-r) % mult_r, (-c) % mult_c
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


def fused_fake_quant_eligible(spec: Optional[QuantSpec],
                              x: jnp.ndarray) -> bool:
    """Can :func:`fused_fake_quant` stand in for
    ``core.quantizer.fake_quant_nograd`` on this call?  The kernel covers the
    hot training shapes: 2-D+ inputs, symmetric nearest-rounded specs with no
    block-wise / sqrt-domain codec."""
    return (spec is not None and x.ndim >= 2 and spec.symmetric
            and spec.block_size == 0 and not spec.sqrt_domain
            and spec.round_mode is RoundMode.NEAREST)


@partial(jax.jit, static_argnames=("spec", "interpret"))
def fused_fake_quant(x: jnp.ndarray, spec: QuantSpec,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Pallas-fused equivalent of core.quantizer.fake_quant_nograd for 2D+
    inputs with symmetric specs (the hot training path)."""
    interp = resolve_interpret(interpret)
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    r, c = x2.shape
    x2p = _pad_to(x2, 8, 128)
    if spec.granularity is Granularity.PER_TOKEN:
        out = _qdq.qdq_row(x2p, spec.bits, interpret=interp)
    else:
        xf = x2.astype(jnp.float32)
        if spec.granularity is Granularity.PER_CHANNEL:
            absmax = jnp.max(jnp.abs(xf), axis=0, keepdims=True)
            scale = jnp.maximum(absmax, 1e-12) / spec.qmax
            scale = _pad_to(scale, 1, 128)
            # padded columns get scale 0 -> guard
            scale = jnp.where(scale == 0, 1.0, scale)
        else:
            absmax = jnp.max(jnp.abs(xf))
            scale = (jnp.maximum(absmax, 1e-12) / spec.qmax).reshape(1, 1)
        out = _qdq.qdq_scaled(x2p, scale, spec.bits, interpret=interp)
    return out[:r, :c].reshape(shape)


def int8_payload_linear(xq: jnp.ndarray, x_scale: jnp.ndarray,
                        wq: jnp.ndarray, w_scale: jnp.ndarray,
                        out_dtype=jnp.bfloat16,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Rank-1-dequant int8 matmul on *pre-quantized* operands: ``xq`` (M, K)
    int8 + per-token/per-tensor ``x_scale``, ``wq`` (K, N) int8 + per-channel/
    per-tensor ``w_scale``.  The shared core of the spec-driven, prepared and
    custom-vjp forward entries -- padding to MXU blocks, scale broadcast to
    the kernel's (M,1) x (1,N) layout, and the output slice live here once."""
    interp = resolve_interpret(interpret)
    m, n = xq.shape[0], wq.shape[1]
    row_scale = jnp.broadcast_to(x_scale.astype(jnp.float32).reshape(-1, 1),
                                 (m, 1))
    col_scale = jnp.broadcast_to(w_scale.astype(jnp.float32).reshape(1, -1),
                                 (1, n))
    out = _mm.int8_matmul(_pad_to(xq, 128, 128), _pad_to(wq, 128, 128),
                          _pad_to(row_scale, 128, 1),
                          _pad_to(col_scale, 1, 128),
                          out_dtype=out_dtype, interpret=interp)
    return out[:m, :n]


def int8_linear(x: jnp.ndarray, w: jnp.ndarray, a_spec: QuantSpec,
                w_spec: QuantSpec, out_dtype=None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Spec-driven real-int8 linear: quantize x per ``a_spec`` (per-token or
    per-tensor) and w per ``w_spec`` (per-channel or per-tensor), run the int8
    MXU matmul, apply the rank-1 dequant epilogue.  x: (..., K); w: (K, N).

    Integer payloads come from ``core.quantizer.quantize_int`` -- the same
    codec behind ``fake_quant_nograd`` -- so a backward pass built on the
    fake-quant residuals sees exactly what the kernel multiplied, by
    construction.  Caller gates eligibility (symmetric 8-bit, no blocking)
    -- see ``core.qlinear.int8_backend_supported``.
    """
    out_dtype = out_dtype or x.dtype
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    xq, row_scale, _ = quantize_int(x2, a_spec)     # zero == 0 (symmetric)
    wq, col_scale, _ = quantize_int(w, w_spec)
    out = int8_payload_linear(xq, row_scale, wq, col_scale,
                              out_dtype=out_dtype, interpret=interpret)
    return out.reshape(*shape[:-1], w.shape[1])


def int8_prepared_linear(x: jnp.ndarray, wq: jnp.ndarray,
                         w_scale: jnp.ndarray, a_spec: QuantSpec,
                         out_dtype=None,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """Real-int8 linear consuming a *pre-quantized* weight: ``wq`` (K, N)
    int8 payload and ``w_scale`` (1, N) fp32 (quantized once at engine
    construction, ``repro.infer.prepare``).  Only the activations are
    quantized in-trace, so the decode step's HLO carries no weight absmax /
    round -- the serving half of the paper's W8A8 recipe."""
    out_dtype = out_dtype or x.dtype
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    xq, row_scale, _ = quantize_int(x2, a_spec)     # zero == 0 (symmetric)
    out = int8_payload_linear(xq, row_scale, wq, w_scale,
                              out_dtype=out_dtype, interpret=interpret)
    return out.reshape(*shape[:-1], wq.shape[1])


# ---------------------------------------------------------------------------
# Training backward: both matmuls on the int8 MXU path, consuming the stored
# forward payloads.  The scale algebra that keeps the epilogues rank-1:
#
#   dx[m,k] = sum_n g[m,n] * (w_int[k,n]*sw[n])     fold sw into g, quantize
#           ~= sh[m] * sum_n hq[m,n] * w_int[k,n]   h = g*sw per-TOKEN (sh)
#   dW[k,n] = sum_m (x_int[m,k]*sx[m]) * g[m,n]     fold sx into g, quantize
#           ~= sh[n] * sum_m x_int[m,k] * hq[m,n]   h = g*sx per-CHANNEL (sh)
#
# Folding the counterpart operand's dequant scale into the fp gradient moves
# every scale off the contracted axis, so the int32 accumulators dequantize
# with one broadcast multiply -- and the int8 residual payloads are consumed
# exactly as stored.  The absmax reduce runs outside (one fused XLA pass over
# g, nothing materialized); round/clip/cast run inside the kernel grid.
# ---------------------------------------------------------------------------

def int8_bwd_dx(g: jnp.ndarray, wq: jnp.ndarray, w_scale: jnp.ndarray,
                out_dtype=None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """dx = qdq_token(g * w_scale) @ wq^T.  g: fp (M, N); wq: int8 (K, N)
    stored forward payload; w_scale: fp32 per-channel (1, N) or per-tensor
    (1, 1) -> (M, K) out_dtype."""
    interp = resolve_interpret(interpret)
    out_dtype = out_dtype or g.dtype
    m, n = g.shape
    k = wq.shape[0]
    with jax.named_scope("qdq"):
        fold = jnp.broadcast_to(w_scale.astype(jnp.float32).reshape(1, -1),
                                (1, n))
        absmax = jnp.max(jnp.abs(g.astype(jnp.float32)) * fold, axis=1,
                         keepdims=True)
        q_scale = jnp.maximum(absmax, _EPS) / 127.0
    out = _mm.int8_matmul_nt(_pad_to(g, 128, 128), _pad_to(wq, 128, 128),
                             _pad_to(fold, 1, 128), _pad_to(q_scale, 128, 1),
                             out_dtype=out_dtype, interpret=interp)
    return out[:m, :k]


def int8_bwd_dw(xq: jnp.ndarray, x_scale: jnp.ndarray, g: jnp.ndarray,
                out_dtype=jnp.float32,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """dW = xq^T @ qdq_channel(g * x_scale).  xq: int8 (M, K) stored forward
    payload; x_scale: fp32 per-token (M, 1) or per-tensor (1, 1); g: fp
    (M, N) -> (K, N) out_dtype."""
    interp = resolve_interpret(interpret)
    m, n = g.shape
    k = xq.shape[1]
    with jax.named_scope("qdq"):
        fold = jnp.broadcast_to(x_scale.astype(jnp.float32).reshape(-1, 1),
                                (m, 1))
        absmax = jnp.max(jnp.abs(g.astype(jnp.float32)) * fold, axis=0,
                         keepdims=True)
        q_scale = jnp.maximum(absmax, _EPS) / 127.0
    out = _mm.int8_matmul_tn(_pad_to(xq, 128, 128), _pad_to(g, 128, 128),
                             _pad_to(fold, 128, 1), _pad_to(q_scale, 1, 128),
                             out_dtype=out_dtype, interpret=interp)
    # A layer scan stacks dW per layer, and the TPU compiler fuses the
    # kernel into that update; the fusion gets the default 16 MiB of scoped
    # VMEM whatever the kernel asks for, which the picked tiles exceed.
    # The barrier keeps the kernel a call of its own.
    return jax.lax.optimization_barrier(out)[:k, :n]


@partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def int8_quantized_matmul(x: jnp.ndarray, w: jnp.ndarray,
                          out_dtype=jnp.bfloat16,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Real-int8 W8A8 linear with the paper's recommended granularity pair
    baked in: per-token x, per-channel w (int8_linear with fixed specs)."""
    return int8_linear(x, w, QuantSpec(8, Granularity.PER_TOKEN),
                       QuantSpec(8, Granularity.PER_CHANNEL),
                       out_dtype=out_dtype, interpret=interpret)
