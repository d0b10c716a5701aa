"""Plain float32 GPT-2 (Radford et al. 2019, "Language Models are
Unsupervised Multitask Learners"; the layout of the Hugging Face ``gpt2``
config): learned positions, pre-LayerNorm blocks, biased projections, a
two-layer tanh-GELU MLP and a head tied to the token embedding.

Layer weights are stacked along a leading layer axis, matrices stored as
(in, out).  Departures from the published model: the embedding table has
``vocab_rows`` rows (the padding rows are never used), nothing else.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import (causal_attention, cross_entropy_sum,
                                        fill, mm, vocab_rows)


def dims(conf: Dict) -> Dict:
    d = conf["n_embd"]
    return dict(L=conf["n_layer"], d=d, h=conf["n_head"],
                ff=conf.get("n_inner") or 4 * d, V=conf["vocab_size"],
                P=conf["n_positions"], eps=conf["layer_norm_epsilon"],
                rows=vocab_rows(conf["vocab_size"]))


def shapes(conf: Dict) -> Dict:
    m = dims(conf)
    L, d, ff = m["L"], m["d"], m["ff"]
    ln = lambda *lead: {"scale": lead + (d,), "bias": lead + (d,)}
    return {
        "embed": (m["rows"], d),
        "pos_embed": (m["P"], d),
        "blocks": {
            "ln1": ln(L), "ln2": ln(L),
            "attn": {"wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d),
                     "wo": (L, d, d), "bq": (L, d), "bk": (L, d),
                     "bv": (L, d), "bo": (L, d)},
            "mlp": {"w_fc1": (L, d, ff), "w_fc2": (L, ff, d),
                    "b_fc1": (L, ff), "b_fc2": (L, d)},
        },
        "final_norm": ln(),
    }


def init(key, conf: Dict) -> Dict:
    """Random weights at GPT-2's initial scales: tables N(0, 0.02) and
    N(0, 0.01), projections N(0, 1/fan_in) with the residual outputs
    further scaled by 1/sqrt(2L), LayerNorm gains 1 + N(0, 0.1) and biases
    N(0, 0.02) (non-zero, so that a dropped bias shows)."""
    m = dims(conf)
    out_scale = 1.0 / math.sqrt(2 * m["L"])

    def rule(name, shape):
        if name == "embed":
            return "normal", 0.02
        if name == "pos_embed":
            return "normal", 0.01
        if name == "scale":
            return "gain", 0.1
        if len(shape) <= 2 and name != "embed":      # biases, (L, n) or (n,)
            return "normal", 0.02
        std = 1.0 / math.sqrt(shape[-2])
        return "normal", std * (out_scale if name in ("wo", "w_fc2") else 1.0)
    return fill(key, shapes(conf), rule)


def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def hidden(params, inp, conf: Dict):
    """Final hidden states (S, d) of token ids ``inp`` (S,)."""
    m = dims(conf)
    s = inp.shape[0]
    x = params["embed"][inp] + params["pos_embed"][:s]
    hd = m["d"] // m["h"]
    blocks = params["blocks"]

    @jax.checkpoint
    def layer(x, lp):
        a = lp["attn"]
        y = _ln(x, lp["ln1"], m["eps"])
        q = (mm(y, a["wq"]) + a["bq"]).reshape(s, m["h"], hd)
        k = (mm(y, a["wk"]) + a["bk"]).reshape(s, m["h"], hd)
        v = (mm(y, a["wv"]) + a["bv"]).reshape(s, m["h"], hd)
        x = x + mm(causal_attention(q, k, v), a["wo"]) + a["bo"]
        y = _ln(x, lp["ln2"], m["eps"])
        f = lp["mlp"]
        u = jax.nn.gelu(mm(y, f["w_fc1"]) + f["b_fc1"], approximate=True)
        return x + mm(u, f["w_fc2"]) + f["b_fc2"]

    for i in range(m["L"]):
        x = layer(x, jax.tree_util.tree_map(lambda t: t[i], blocks))
    return _ln(x, params["final_norm"], m["eps"])


def seq_loss(params, row, conf: Dict):
    """Summed cross entropy of one (S+1,) token row."""
    x = hidden(params, row[:-1], conf)
    return cross_entropy_sum(x, params["embed"].T, row[1:],
                             dims(conf)["V"])

