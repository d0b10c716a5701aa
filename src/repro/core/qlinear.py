"""Quantized linear layer with the paper's Fig-1 forward/backward semantics.

Forward  : y = qdq_A(x) @ qdq_W(w)
Backward : dx = g        @ qdq_W(w)^T        (REAL-valued g -- paper finds that
                                              propagating quantization error
                                              through the input-gradient path
                                              destabilizes training, Fig. 10)
           dW = qdq_A(x)^T @ qdq_G(g)        (output-grad quantized ONLY on the
                                              weight-update path)
STE everywhere: the w / x cotangents pass straight through their quantizers.

``grads_dx`` in the recipe turns on the paper's instability ablation where the
dx path also sees quantized gradients.

Two implementations share these semantics:

* the fake-quant reference (fp einsums over qdq'd tensors -- the paper's
  simulation methodology; symmetric nearest codecs store their custom-vjp
  residuals as int8 QState payloads and dequantize-on-read, ~4x less residual
  memory with bit-identical values, no kernel dependency), and
* the real-int8 Pallas path (:func:`int8_quantized_linear`): the forward
  quantizes each operand ONCE into int8 payload + scales, runs the W8A8 MXU
  kernel, and threads the payloads through as custom_vjp residuals (~4x less
  residual memory than qdq'd fp copies).  When the recipe also carries an
  in-contract G8 spec (:func:`int8_bwd_supported`) both backward matmuls run
  on transposed int8 kernels against the stored payloads; otherwise the
  backward dequantizes-on-read and replays the reference vjp bit-exactly.
"""
from __future__ import annotations

import os
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qadam import QState
from repro.core.qconfig import Granularity, QuantRecipe, RoundMode
from repro.core.quantizer import (dequantize_int, fake_quant_nograd,
                                  maybe_fake_quant, quantize_int)


def _flat2d(a: jnp.ndarray) -> jnp.ndarray:
    return a.reshape(-1, a.shape[-1])


@jax.named_scope("qdq")
def _train_fake_quant(x: jnp.ndarray, spec, key=None) -> jnp.ndarray:
    """``fake_quant_nograd`` with the hot symmetric 2-D cases routed through
    the fused Pallas kernel (one HBM round trip instead of three -- see
    kernels/qdq.py).  The route engages where the kernel actually compiles
    (TPU); under interpret mode (CPU) the reference einsum is both the oracle
    and the faster path.  ``REPRO_FUSED_FQ=1/0`` forces the choice either way
    (tests pin ``1`` to exercise the routed path off-TPU)."""
    force = os.environ.get("REPRO_FUSED_FQ", "")
    fused = (force == "1") if force in ("0", "1") \
        else jax.default_backend() == "tpu"
    if fused and key is None:
        from repro.kernels import ops              # lazy: pallas import
        if ops.fused_fake_quant_eligible(spec, x):
            return ops.fused_fake_quant(x, spec)
    return fake_quant_nograd(x, spec, key)


def residual_compressible(spec) -> bool:
    """Can the custom-vjp residual for this operand be stored as an int8
    ``QState`` (payload + scales) instead of the qdq'd fp copy?  Requires a
    codec whose ``dequantize_int(quantize_int(x))`` reproduces
    ``fake_quant_nograd(x)`` bit-exactly: symmetric (zero == 0 by
    construction, so only scale multiplies on read), nearest rounding (no key
    stream to replay), <= 8 bits (int8 payload), no sqrt domain.  Blockwise
    codecs qualify -- the stored shape recovers the tail padding."""
    return (spec is not None and spec.symmetric
            and spec.round_mode is RoundMode.NEAREST
            and spec.bits <= 8 and not spec.sqrt_domain)


def _encode_residual(t: jnp.ndarray, spec):
    """(value the matmul consumes, residual to store).  Compressible specs
    pay the quantize ONCE and keep the int8 payload (~4x smaller residual --
    the PR-3 trick, no kernel dependency); everything else stores the qdq'd
    fp tensor as before."""
    if spec is None:
        return t, t
    if residual_compressible(spec):
        q, scale, zero = quantize_int(t, spec)
        deq = dequantize_int(q, scale, zero, spec, shape=t.shape,
                             dtype=t.dtype)
        return deq, QState(q, scale, zero)
    tq = _train_fake_quant(t, spec)
    return tq, tq


def _decode_residual(res, spec, shape, dtype) -> jnp.ndarray:
    """Dequantize-on-read: recover the exact tensor the forward matmul
    consumed from either residual representation."""
    if isinstance(res, QState):
        return dequantize_int(res.q, res.scale, res.zero, spec, shape=shape,
                              dtype=dtype)
    return res


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qlinear(x: jnp.ndarray, w: jnp.ndarray, key, recipe: QuantRecipe):
    xq = maybe_fake_quant(x, recipe.acts)
    wq = maybe_fake_quant(w, recipe.weights)
    return jnp.matmul(xq, wq)


def _qlinear_fwd(x, w, key, recipe):
    # Error injection happens here; the residuals hold the *quantized*
    # tensors (they are what the matmul actually consumed) -- as int8
    # QState payloads when the codec allows, qdq'd fp copies otherwise.
    xv, xr = _encode_residual(x, recipe.acts)
    wv, wr = _encode_residual(w, recipe.weights)
    y = jnp.matmul(xv, wv)
    return y, (xr, wr, key, x.shape, w.shape,
               jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype))


def _qlinear_bwd(recipe, res, g):
    xr, wr, key, x_shape, w_shape, x_proto, w_proto = res
    xq = _decode_residual(xr, recipe.acts, x_shape, x_proto.dtype)
    wq = _decode_residual(wr, recipe.weights, w_shape, w_proto.dtype)
    return _qlinear_bwd_core(recipe, xq, wq, key, x_shape, g)


def _qlinear_bwd_core(recipe, xq, wq, key, x_shape, g):
    """Reference Fig-1 vjp over the (dequantized) forward operands -- shared
    by the fake-quant path and the int8 path's out-of-contract fallback."""
    # Independent subkeys per backward path: when both grads_dx and grads are
    # stochastic, the dW rounding noise must be uncorrelated with the dx
    # noise (and neither path may consume the caller's parent key raw).
    k_dx = k_dw = None
    if key is not None:
        key_dx, key_dw = jax.random.split(key)
        if (recipe.grads_dx is not None
                and recipe.grads_dx.round_mode is RoundMode.STOCHASTIC):
            k_dx = key_dx
        if (recipe.grads is not None
                and recipe.grads.round_mode is RoundMode.STOCHASTIC):
            k_dw = key_dw

    # --- dx path: real-valued output gradient (paper Fig. 1). -------------
    g_dx = g
    if recipe.grads_dx is not None:                      # instability ablation
        g_dx = _train_fake_quant(g, recipe.grads_dx, k_dx)
    dx = jnp.matmul(g_dx, wq.T).reshape(x_shape)

    # --- dW path: quantized output gradient. ------------------------------
    g_dw = g
    if recipe.grads is not None:
        g_dw = _train_fake_quant(g, recipe.grads, k_dw)
    g2 = _flat2d(g_dw)
    x2 = _flat2d(xq)
    dw = jax.lax.dot_general(
        x2, g2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(wq.dtype)

    key_ct = (None if key is None
              else np.zeros(key.shape, dtype=jax.dtypes.float0))
    return dx, dw, key_ct


_qlinear.defvjp(_qlinear_fwd, _qlinear_bwd)


def quantized_linear(x: jnp.ndarray, w: jnp.ndarray, recipe: Optional[QuantRecipe],
                     key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Public entry point.  Falls back to a plain matmul when the recipe does
    not quantize any linear-layer component (keeps the fp baseline's HLO free
    of custom_vjp scaffolding)."""
    if recipe is None or not recipe.any_linear_quant:
        return jnp.matmul(x, w)
    return _qlinear(x, w, key, recipe)


# ---------------------------------------------------------------------------
# Real-int8 backend: the Pallas W8A8 kernel replaces the fake-quant einsum on
# the forward, each operand is quantized exactly ONCE and threaded through as
# an int8 QState residual (payload + scales, ~4x smaller than the qdq'd fp
# copies), and -- when the recipe carries an in-contract G8 spec -- both
# backward matmuls run on the transposed int8 kernels against those stored
# payloads.  Out-of-contract backwards dequantize-on-read and replay the
# reference Fig-1 vjp (dequantize_int reproduces fake_quant_nograd
# bit-exactly: same scale, round, clip, cast).
# ---------------------------------------------------------------------------

_INT8_GRANS_W = (Granularity.PER_CHANNEL, Granularity.PER_TENSOR)
_INT8_GRANS_A = (Granularity.PER_TOKEN, Granularity.PER_TENSOR)


def int8_backend_supported(recipe: Optional[QuantRecipe]) -> bool:
    """True when the recipe's forward is expressible as the int8 kernel's
    rank-1-rescale W8A8 contract: symmetric 8-bit weights+acts, nearest
    rounding, no block-wise codec (per-tensor/per-channel W x per-tensor/
    per-token A)."""
    if recipe is None:
        return False
    w, a = recipe.weights, recipe.acts
    return (w is not None and a is not None
            and w.bits == 8 and a.bits == 8
            and w.symmetric and a.symmetric
            and w.block_size == 0 and a.block_size == 0
            and not w.sqrt_domain and not a.sqrt_domain
            and w.round_mode is RoundMode.NEAREST
            and a.round_mode is RoundMode.NEAREST
            and w.granularity in _INT8_GRANS_W
            and a.granularity in _INT8_GRANS_A)


def int8_decode_attn_supported(spec) -> bool:
    """True when the fused Pallas decode-attention / q8-prefill kernels can
    consume a KV cache stored under ``spec`` (see kernels/decode_attn.py):
    symmetric 8-bit nearest-rounded PER_TOKEN -- one scale per (position,
    head) row, the sidecar layout the kernels fold in-register.  Per-tensor
    KV specs scale per *slot write block* (a reduction across heads and
    positions that cannot map onto the per-(slot, head) kernel grid) and stay
    on the dequantize-on-read reference path."""
    return (spec is not None and spec.bits == 8 and spec.symmetric
            and spec.block_size == 0 and not spec.sqrt_domain
            and spec.round_mode is RoundMode.NEAREST
            and spec.granularity is Granularity.PER_TOKEN)


def int8_bwd_supported(recipe: Optional[QuantRecipe]) -> bool:
    """True when the backward is expressible as the transposed int8 kernels'
    contract: the forward contract plus a symmetric 8-bit nearest-rounded
    PER_TOKEN gradient spec and no dx-path ablation.

    The hardware path necessarily quantizes the output gradient on *both*
    backward matmuls (an int8 dot needs two int8 operands); the paper's
    Fig-1 semantics of a real-valued dx-path gradient survive only up to
    that 8-bit per-token rounding of g (with the weight scales folded in).
    Recipes outside this contract -- no G spec (fp dW path), stochastic
    rounding, ``grads_dx`` ablations, coarser granularities -- fall back to
    the reference vjp on dequantized residuals.
    """
    if not int8_backend_supported(recipe):
        return False
    g = recipe.grads
    return (g is not None and recipe.grads_dx is None
            and g.bits == 8 and g.symmetric
            and g.block_size == 0 and not g.sqrt_domain
            and g.round_mode is RoundMode.NEAREST
            and g.granularity is Granularity.PER_TOKEN)


def _int8_forward(x, w, recipe):
    from repro.kernels.ops import int8_linear    # lazy: pallas import
    return int8_linear(x, w, recipe.acts, recipe.weights, out_dtype=x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _qlinear_int8(x: jnp.ndarray, w: jnp.ndarray, key, recipe: QuantRecipe):
    return _int8_forward(x, w, recipe)


def _qlinear_int8_fwd(x, w, key, recipe):
    from repro.kernels.ops import int8_payload_linear   # lazy: pallas import
    x2 = _flat2d(x)
    xq, x_scale, _ = quantize_int(x2, recipe.acts)      # zero == 0 (symmetric)
    wq, w_scale, _ = quantize_int(w, recipe.weights)
    y = int8_payload_linear(xq, x_scale, wq, w_scale, out_dtype=x.dtype)
    y = y.reshape(*x.shape[:-1], w.shape[-1])
    # Residuals are the int8 payloads the MXU actually consumed -- stored as
    # QState (the optimizer-state / prepared-weight container) plus 0-size
    # dtype carriers so the backward can emit exactly-typed cotangents.
    zero = jnp.zeros((), jnp.float32)
    res = (QState(xq, x_scale, zero), QState(wq, w_scale, zero), key, x.shape,
           jnp.zeros((0,), x.dtype), jnp.zeros((0,), w.dtype))
    return y, res


def _qlinear_int8_bwd(recipe, res, g):
    xs, ws, key, x_shape, x_proto, w_proto = res
    if int8_bwd_supported(recipe):
        from repro.kernels.ops import int8_bwd_dw, int8_bwd_dx   # lazy
        g2 = _flat2d(g)
        dx = int8_bwd_dx(g2, ws.q, ws.scale,
                         out_dtype=x_proto.dtype).reshape(x_shape)
        dw = int8_bwd_dw(xs.q, xs.scale, g2, out_dtype=w_proto.dtype)
        key_ct = (None if key is None
                  else np.zeros(key.shape, dtype=jax.dtypes.float0))
        return dx, dw, key_ct
    # Out-of-contract recipe (fp dW path, stochastic g, grads_dx ablation,
    # coarse granularity): dequantize-on-read and replay the reference vjp.
    xq = dequantize_int(xs.q, xs.scale, xs.zero, recipe.acts,
                        dtype=x_proto.dtype)
    wq = dequantize_int(ws.q, ws.scale, ws.zero, recipe.weights,
                        dtype=w_proto.dtype)
    return _qlinear_bwd_core(recipe, xq, wq, key, x_shape, g)


_qlinear_int8.defvjp(_qlinear_int8_fwd, _qlinear_int8_bwd)


def int8_quantized_linear(x: jnp.ndarray, w: jnp.ndarray, recipe: QuantRecipe,
                          key: Optional[jax.Array] = None) -> jnp.ndarray:
    """W8A8 linear with real integer compute (TPU MXU path; interpret-mode on
    CPU): always on the forward, and on both backward matmuls too when
    :func:`int8_bwd_supported` accepts the recipe.  Caller must check
    :func:`int8_backend_supported`; unsupported recipes should route to
    :func:`quantized_linear` instead."""
    if not int8_backend_supported(recipe):
        raise ValueError(
            f"recipe [{recipe.describe() if recipe else 'fp'}] is outside the "
            "int8 kernel contract; use quantized_linear")
    return _qlinear_int8(x, w, key, recipe)
