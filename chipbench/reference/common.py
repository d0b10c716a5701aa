"""Pieces shared by the plain references: initialisation, causal attention
in query blocks, the cross entropy, the batch loss, and the operation
counts of ``chipbench.flops``' convention."""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: query rows per attention block; bounds the (heads, rows, keys) score slab
Q_BLOCK = 512


def mm(a, b):
    """float32 matrix product at full precision (the TPU otherwise runs
    float32 products in bfloat16 passes)."""
    return jnp.matmul(a, b, precision=HIGHEST)


def vocab_rows(vocab: int) -> int:
    """Rows of the embedding and head tables: the vocabulary rounded up to
    a multiple of 128.  The padding rows are never looked up and their
    logits are excluded from the softmax."""
    return -(-vocab // 128) * 128


def fill(key, shapes: Dict, rule: Callable[[str, tuple], tuple]) -> Dict:
    """Fill a nested dict of shapes from ``key``.  ``rule(name, shape)``
    gives ``(kind, scale)`` with kind ``normal`` (mean 0) or ``gain``
    (mean 1).  Leaves are keyed by their sorted path, so the values do not
    depend on the order of the dict."""
    flat = []

    def walk(prefix, tree):
        for k in sorted(tree):
            if isinstance(tree[k], dict):
                walk(prefix + (k,), tree[k])
            else:
                flat.append((prefix + (k,), tree[k]))
    walk((), shapes)
    out: Dict = {}
    for i, (path, shape) in enumerate(flat):
        kind, scale = rule(path[-1], shape)
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        x = x * scale + (1.0 if kind == "gain" else 0.0)
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    return out


def stacked_matrix_weights(blocks: Dict) -> int:
    """Weights of every matrix in a nested dict of shapes stacked along a
    leading layer axis: each (L, in, out) leaf counts L * in * out; biases
    and gains, (L, n), are no matrices."""
    tot = 0
    for v in blocks.values():
        if isinstance(v, dict):
            tot += stacked_matrix_weights(v)
        elif len(v) == 3:
            tot += v[0] * v[1] * v[2]
    return tot


def attention_ops(layers: int, seq: int, heads: int, d_qk: int,
                  d_v: int) -> float:
    """Training operations per token of the attention scores and their use
    at context ``seq``: 6 * L * s * h * (d_qk + d_v)."""
    return 6.0 * layers * seq * heads * (d_qk + d_v)


def causal_attention(q, k, v):
    """q: (S, H, hd); k, v: (S, H, hd) with kv heads already repeated.
    Softmax over keys in float32, query rows in blocks of :data:`Q_BLOCK`."""
    s, h, hd = q.shape
    blk = min(Q_BLOCK, s)
    assert s % blk == 0, (s, blk)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST)
        sc = sc / math.sqrt(hd)
        qpos = i * blk + jnp.arange(blk)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    out = jax.lax.map(one, jnp.arange(s // blk))
    return out.reshape(s, h * hd)


def cross_entropy_sum(h, head, labels, vocab: int):
    """Sum over positions of -log softmax(h @ head)[label], with ``head``
    (d, rows) and the logits of rows >= ``vocab`` left out."""
    logits = mm(h, head[:, :vocab])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold)


def batch_loss(seq_loss: Callable, params, tokens):
    """Mean next-token cross entropy over a (B, S+1) batch, one sequence
    at a time so that only one sequence's activations are alive."""
    b, s1 = tokens.shape

    def body(tot, row):
        return tot + jax.checkpoint(seq_loss)(params, row), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), tokens)
    return tot / (b * (s1 - 1))
