"""Plain float32 Yi decoder (01.AI, arXiv:2403.04652; the layout of the
Hugging Face ``01-ai/Yi-6B`` config): RMSNorm pre-norm blocks, rotary
positions (half split, theta from the config), grouped-query attention with
no biases, a SiLU-gated MLP, and an untied output head.

Layer weights are stacked along a leading layer axis, matrices stored as
(in, out).  Query head j reads key/value head j // (heads / kv_heads).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import (attention_ops, causal_attention,
                                        cross_entropy_sum, fill, mm,
                                        stacked_matrix_weights, vocab_rows)


def dims(conf: Dict) -> Dict:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return dict(L=conf["num_hidden_layers"], d=d, h=h,
                kv=conf["num_key_value_heads"], hd=d // h,
                ff=conf["intermediate_size"], V=conf["vocab_size"],
                eps=conf["rms_norm_eps"], theta=conf["rope_theta"],
                rows=vocab_rows(conf["vocab_size"]))


def shapes(conf: Dict) -> Dict:
    m = dims(conf)
    L, d, ff = m["L"], m["d"], m["ff"]
    qd, kd = m["h"] * m["hd"], m["kv"] * m["hd"]
    return {
        "embed": (m["rows"], d),
        "blocks": {
            "ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)},
            "attn": {"wq": (L, d, qd), "wk": (L, d, kd), "wv": (L, d, kd),
                     "wo": (L, qd, d)},
            "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                    "w_down": (L, ff, d)},
        },
        "final_norm": {"scale": (d,)},
        "lm_head": (d, m["rows"]),
    }


def init(key, conf: Dict) -> Dict:
    """Embedding N(0, 0.02), projections and head N(0, 1/fan_in) with the
    residual outputs further scaled by 1/sqrt(2L), RMSNorm gains
    1 + N(0, 0.1)."""
    m = dims(conf)
    out_scale = 1.0 / math.sqrt(2 * m["L"])

    def rule(name, shape):
        if name == "embed":
            return "normal", 0.02
        if name == "scale":
            return "gain", 0.1
        std = 1.0 / math.sqrt(shape[-2])
        return "normal", std * (out_scale if name in ("wo", "w_down") else 1.0)
    return fill(key, shapes(conf), rule)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd); rotate the two halves of each head."""
    s, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def seq_loss(params, row, conf: Dict):
    """Summed cross entropy of one (S+1,) token row."""
    m = dims(conf)
    inp, labels = row[:-1], row[1:]
    s = inp.shape[0]
    x = params["embed"][inp]
    rep = m["h"] // m["kv"]
    blocks = params["blocks"]

    @jax.checkpoint
    def layer(x, lp):
        a = lp["attn"]
        y = _rms(x, lp["ln1"]["scale"], m["eps"])
        q = _rope(mm(y, a["wq"]).reshape(s, m["h"], m["hd"]), m["theta"])
        k = _rope(mm(y, a["wk"]).reshape(s, m["kv"], m["hd"]), m["theta"])
        v = mm(y, a["wv"]).reshape(s, m["kv"], m["hd"])
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        x = x + mm(causal_attention(q, k, v), a["wo"])
        y = _rms(x, lp["ln2"]["scale"], m["eps"])
        f = lp["mlp"]
        u = jax.nn.silu(mm(y, f["w_gate"])) * mm(y, f["w_up"])
        return x + mm(u, f["w_down"])

    for i in range(m["L"]):
        x = layer(x, jax.tree_util.tree_map(lambda t: t[i], blocks))
    x = _rms(x, params["final_norm"]["scale"], m["eps"])
    return cross_entropy_sum(x, params["lm_head"], labels, m["V"])


def train_ops_per_token(conf: Dict, seq: int) -> Dict[str, float]:
    """``linears``: 6 per weight of the blocks' projections and gated MLP;
    ``other``: the untied head and attention (``chipbench.flops``)."""
    m = dims(conf)
    return {"linears": 6.0 * stacked_matrix_weights(shapes(conf)["blocks"]),
            "other": 6.0 * m["d"] * m["V"]
            + attention_ops(m["L"], seq, m["h"], m["hd"], m["hd"])}


def tiny(conf: Dict, d: int) -> Dict:
    """``conf`` cut for the CPU tests: 2 layers of width ``d``, grouped
    queries kept (4 heads over 2 key/value heads), a gated MLP of 3d and a
    vocabulary of 4d + 44, in the file's keys and the program's fields
    alike."""
    conf = dict(conf, num_hidden_layers=2, hidden_size=d,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=3 * d, vocab_size=4 * d + 44)
    prog = conf["program"]
    conf["program"] = dict(prog, fields=dict(
        prog["fields"], n_layers=2, d_model=d, n_heads=4, n_kv_heads=2,
        head_dim=d // 4, d_ff=3 * d, vocab_size=4 * d + 44))
    return conf
