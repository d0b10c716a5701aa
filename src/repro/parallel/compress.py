"""Int8-compressed gradient all-reduce (beyond-paper, DESIGN.md Section 2).

The paper cites Markov et al. 2023 (quantized distributed training) as the
bandwidth-saving direction for gradient quantization; this implements the
TPU-idiomatic version with shard_map:

  1. split the flat gradient into n_dev chunks;
  2. quantize each chunk to int8 with a per-chunk fp32 scale (symmetric
     absmax -- the paper's Eq. 1);
  3. all_to_all the quantized chunks (each rank receives every rank's copy of
     ITS chunk);
  4. dequantize + sum locally in fp32 (the reduce);
  5. re-quantize the reduced chunk, all_gather payloads + scales;
  6. dequantize into the full reduced gradient.

Bytes on the wire per device: ~2 * N/n_dev * 1B (int8) vs 2 * N/n_dev * 4B
for a ring all-reduce in fp32 -> ~4x bisection-bandwidth saving, visible in
the roofline collective term.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def _quant_chunks(x: jnp.ndarray, qmax: int = 127
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (n, chunk) -> (int8 (n, chunk), scales (n, 1))."""
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / qmax
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    return q, scale


def _dequant(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def int8_psum_flat(flat: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Per-shard body: compressed psum of a replicated flat fp32 vector.
    flat length must be divisible by the axis size."""
    n = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    chunks = flat.reshape(n, -1)                     # (n_dev, chunk)
    q, s = _quant_chunks(chunks)
    # all_to_all: rank r receives every rank's chunk r
    q_r = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
    s_r = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0)
    reduced = jnp.sum(_dequant(q_r, s_r), axis=0)    # (chunk,)
    q2, s2 = _quant_chunks(reduced[None, :])
    q_all = jax.lax.all_gather(q2[0], axis_name, axis=0)      # (n, chunk)
    s_all = jax.lax.all_gather(s2[0], axis_name, axis=0)      # (n, 1)
    return _dequant(q_all, s_all).reshape(flat.shape)


def compressed_allreduce(tree, mesh: Mesh, axis_name: str):
    """All-reduce (sum) a gradient pytree with int8 wire format.

    Inputs are replicated along ``axis_name`` holding per-shard partial
    gradients conceptually; exposed for shard_map-based DP train-step
    variants and benchmarked for the collective-bound hillclimb cell
    (tests/test_distributed.py pins parity against the fp ``psum``).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [x.size for x in leaves]
    flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                            for x in leaves])

    def body(v):
        nn = jax.lax.axis_size(axis_name)
        padlen = (-v.size) % nn
        vp = jnp.pad(v, (0, padlen))
        out = int8_psum_flat(vp, axis_name)
        return out[:v.size]

    out = jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                            check_vma=False)(flat)
    parts = []
    off = 0
    for x, size in zip(leaves, sizes):
        parts.append(out[off:off + size].reshape(x.shape))
        off += size
    return jax.tree_util.tree_unflatten(treedef, parts)
