"""Linear quantization primitives (paper Section 3.1, Eq. 1).

Everything here implements *fake quantization*: values are quantized to the
integer grid and immediately dequantized, so the error is injected while the
computation stays in floating point -- exactly the paper's methodology.  The
integer-storage variants (:func:`quantize_int` / :func:`dequantize_int`) back
the quantized optimizer states and the real-int8 Pallas kernels.

Scale granularity convention (uniform across the codebase):

  * PER_TENSOR  : scalar scale.
  * PER_CHANNEL : one scale per element of the LAST dim (for a weight stored
    as (in, out) that is the output channel; for activations the feature dim;
    the paper's "per-column" for optimizer states).
  * PER_TOKEN   : one scale per row, i.e. reduced over the LAST dim only.

Gradient flow uses the straight-through estimator (STE, Bengio et al. 2013):
d qdq(x)/dx == 1.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.qconfig import Granularity, QuantSpec, RoundMode

_EPS = 1e-12


def _reduce_axes(ndim: int, granularity: Granularity) -> Optional[Tuple[int, ...]]:
    """Axes over which the scale statistic is computed (keepdims=True)."""
    if granularity is Granularity.PER_TENSOR:
        return tuple(range(ndim))
    if granularity is Granularity.PER_CHANNEL:
        # one scale per last-dim element -> reduce everything else
        return tuple(range(ndim - 1))
    if granularity is Granularity.PER_TOKEN:
        # one scale per row -> reduce last dim only
        return (ndim - 1,)
    raise ValueError(granularity)


def compute_scale_zero(x: jnp.ndarray, spec: QuantSpec,
                       axes: Optional[Tuple[int, ...]] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Return (scale, zero_point) with keepdims-shaped leading axes.

    Symmetric (paper default): s = absmax / P, z = 0.
    Asymmetric: full-range affine -- s = (max - min) / (P - N),
    z = round(min / s) - N, so min -> N and max -> P.  (The paper's prose
    formula wastes half the signed range; we use the standard full-range
    affine mapping which is what its asymmetric experiment intends.)

    ``axes`` overrides the granularity-derived reduction axes -- used where
    leading batch/stack dims must each keep their own grid (prepared stacked
    weights, per-slot KV write blocks) so the scale formula lives here once.
    """
    if axes is None:
        axes = _reduce_axes(x.ndim, spec.granularity)
    xf = x.astype(jnp.float32)
    if spec.symmetric:
        absmax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
        scale = jnp.maximum(absmax, _EPS) / spec.qmax
        zero = jnp.zeros_like(scale)
    else:
        xmin = jnp.min(xf, axis=axes, keepdims=True)
        xmax = jnp.max(xf, axis=axes, keepdims=True)
        scale = jnp.maximum(xmax - xmin, _EPS) / (spec.qmax - spec.qmin)
        zero = jnp.round(xmin / scale) - spec.qmin
    return scale, zero


def _round(x: jnp.ndarray, mode: RoundMode, key: Optional[jax.Array]) -> jnp.ndarray:
    if mode is RoundMode.NEAREST:
        return jnp.round(x)
    if key is None:
        raise ValueError("stochastic rounding requires a PRNG key")
    noise = jax.random.uniform(key, x.shape, dtype=x.dtype)
    return jnp.floor(x + noise)


def _fake_quant_raw(x: jnp.ndarray, spec: QuantSpec,
                    key: Optional[jax.Array] = None) -> jnp.ndarray:
    """quantize -> dequantize without STE wrapping (paper Eq. 1)."""
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    scale, zero = compute_scale_zero(xf, spec)
    x_int = jnp.clip(_round(xf / scale, spec.round_mode, key) - zero,
                     spec.qmin, spec.qmax)
    return (scale * (x_int + zero)).astype(orig_dtype)


def _blocked_view(x: jnp.ndarray, block_size: int) -> Tuple[jnp.ndarray, int]:
    """Flatten to (n_blocks, block_size), zero-padding the tail."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block_size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, block_size), n


def _fake_quant_blockwise(x: jnp.ndarray, spec: QuantSpec,
                          key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Beyond-paper: Dettmers-style block-wise quantization.

    The tensor is flattened into contiguous blocks of ``spec.block_size``; each
    block gets its own (asymmetric-capable) scale.  Localizes outliers so one
    large value cannot wipe out the resolution of the whole channel/tensor.
    """
    orig_dtype = x.dtype
    blocks, n = _blocked_view(x.astype(jnp.float32), spec.block_size)
    row_spec = QuantSpec(bits=spec.bits, granularity=Granularity.PER_TOKEN,
                         symmetric=spec.symmetric, round_mode=spec.round_mode)
    deq = _fake_quant_raw(blocks, row_spec, key)
    return deq.reshape(-1)[:n].reshape(x.shape).astype(orig_dtype)


@jax.named_scope("qdq")
def fake_quant_nograd(x: jnp.ndarray, spec: QuantSpec,
                      key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Fake quantization *without* gradient pass-through (used on values that
    are not differentiated through, e.g. optimizer states)."""
    if spec.sqrt_domain:
        # For strictly non-negative tensors (Adam m2).  sqrt expands small
        # magnitudes away from the zero bin (paper Fig. 12 failure mode).
        root = jnp.sqrt(jnp.maximum(x, 0.0))
        q = (_fake_quant_blockwise(root, spec, key) if spec.block_size
             else _fake_quant_raw(root, spec, key))
        return jnp.square(q).astype(x.dtype)
    if spec.block_size:
        return _fake_quant_blockwise(x, spec, key)
    return _fake_quant_raw(x, spec, key)


# ---------------------------------------------------------------------------
# STE-wrapped fake quantization (forward error injection, identity backward).
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def fake_quant(x: jnp.ndarray, spec: QuantSpec,
               key: Optional[jax.Array] = None) -> jnp.ndarray:
    return fake_quant_nograd(x, spec, key)


def _fq_fwd(x, spec, key=None):
    return fake_quant_nograd(x, spec, key), None


def _fq_bwd(spec, _res, g):
    # Straight-through estimator: gradient flows unchanged (key gets None).
    return (g, None)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


def maybe_fake_quant(x: jnp.ndarray, spec: Optional[QuantSpec],
                     key: Optional[jax.Array] = None) -> jnp.ndarray:
    """fp passthrough when the component is not quantized."""
    return x if spec is None else fake_quant(x, spec, key)


# ---------------------------------------------------------------------------
# Integer-storage codec (optimizer states, kernels, compressed collectives).
# ---------------------------------------------------------------------------

def storage_dtype(bits: int):
    return jnp.int8 if bits <= 8 else jnp.int16


@jax.named_scope("qdq")
def quantize_int(x: jnp.ndarray, spec: QuantSpec,
                 key: Optional[jax.Array] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Quantize to real integers.  Returns (q, scale, zero).

    q holds X_int of paper Eq. 1 in int8/int16 storage; sub-byte widths (4-bit)
    occupy the low bits of an int8 (packing is a storage-layer concern; the
    value range is what matters for fidelity).
    """
    if spec.block_size:
        blocks, _ = _blocked_view(x.astype(jnp.float32), spec.block_size)
        row_spec = QuantSpec(bits=spec.bits, granularity=Granularity.PER_TOKEN,
                             symmetric=spec.symmetric, round_mode=spec.round_mode)
        scale, zero = compute_scale_zero(blocks, row_spec)
        q = jnp.clip(_round(blocks / scale, spec.round_mode, key) - zero,
                     spec.qmin, spec.qmax)
        return q.astype(storage_dtype(spec.bits)), scale, zero
    scale, zero = compute_scale_zero(x, spec)
    xf = x.astype(jnp.float32)
    q = jnp.clip(_round(xf / scale, spec.round_mode, key) - zero,
                 spec.qmin, spec.qmax)
    return q.astype(storage_dtype(spec.bits)), scale, zero


@jax.named_scope("qdq")
def dequantize_int(q: jnp.ndarray, scale: jnp.ndarray, zero: jnp.ndarray,
                   spec: QuantSpec, shape=None, dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize_int`.  ``shape`` is required for blockwise
    codecs (to strip tail padding)."""
    deq = scale * (q.astype(jnp.float32) + zero)
    if spec.block_size:
        if shape is None:
            raise ValueError("blockwise dequantize needs the original shape")
        n = 1
        for d in shape:
            n *= d
        deq = deq.reshape(-1)[:n].reshape(shape)
    return deq.astype(dtype)


def quant_error(x: jnp.ndarray, spec: QuantSpec) -> jnp.ndarray:
    """Elementwise |x - qdq(x)| -- used by diagnostics and property tests."""
    return jnp.abs(x.astype(jnp.float32) -
                   fake_quant_nograd(x, spec).astype(jnp.float32))
