"""Plain float32 decoder with sparse experts, for the benchmark's tests
only: it shows that a configuration unlike the benchmark's dense ones is
admitted, counted and classified by new files alone.

RMSNorm pre-norm blocks; causal attention whose query/key heads
(``qk_head_dim``) are wider than its value heads (``v_head_dim``), with no
positions; an MoE layer whose router sends each token to its
``num_experts_per_tok`` best of ``n_routed_experts`` SiLU-gated experts,
weighted by their softmax scores, beside ``n_shared_experts`` that every
token passes through (one gated MLP of their summed width); an untied
head.  Layer weights are stacked along a leading layer axis, matrices
stored as (in, out), routed experts as (layer, expert, in, out).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from chipbench.reference.common import (HIGHEST, attention_ops,
                                        cross_entropy_sum, fill, mm,
                                        vocab_rows)


def dims(conf: Dict) -> Dict:
    f = conf["moe_intermediate_size"]
    return dict(L=conf["num_hidden_layers"], d=conf["hidden_size"],
                h=conf["num_attention_heads"], qk=conf["qk_head_dim"],
                v=conf["v_head_dim"], E=conf["n_routed_experts"],
                k=conf["num_experts_per_tok"], f=f,
                shared=conf["n_shared_experts"] * f, V=conf["vocab_size"],
                eps=conf["rms_norm_eps"], rows=vocab_rows(conf["vocab_size"]))


def shapes(conf: Dict) -> Dict:
    m = dims(conf)
    L, d, E, f, sf = m["L"], m["d"], m["E"], m["f"], m["shared"]
    hq, hv = m["h"] * m["qk"], m["h"] * m["v"]
    return {
        "embed": (m["rows"], d),
        "blocks": {
            "ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)},
            "attn": {"wq": (L, d, hq), "wk": (L, d, hq), "wv": (L, d, hv),
                     "wo": (L, hv, d)},
            "moe": {"router": (L, d, E),
                    "w_gate": (L, E, d, f), "w_up": (L, E, d, f),
                    "w_down": (L, E, f, d)},
            "shared": {"w_gate": (L, d, sf), "w_up": (L, d, sf),
                       "w_down": (L, sf, d)},
        },
        "final_norm": {"scale": (d,)},
        "lm_head": (d, m["rows"]),
    }


def init(key, conf: Dict) -> Dict:
    """Embedding N(0, 0.02), matrices N(0, 1/fan_in), RMSNorm gains
    1 + N(0, 0.1)."""
    def rule(name, shape):
        if name == "embed":
            return "normal", 0.02
        if name == "scale":
            return "gain", 0.1
        return "normal", 1.0 / math.sqrt(shape[-2])
    return fill(key, shapes(conf), rule)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w


def _attention(q, k, v):
    """q, k: (S, H, qk); v: (S, H, v) -> (S, H * v), causal."""
    s = q.shape[0]
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
    sc = sc / math.sqrt(q.shape[-1])
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(s, -1)


def _gated(y, g, u, dn):
    return mm(jax.nn.silu(mm(y, g)) * mm(y, u), dn)


def _moe(y, e, k: int):
    """Every expert on every token, kept where the router picks it."""
    probs = jax.nn.softmax(mm(y, e["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    weight = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]) * top[..., None],
                     axis=-2)
    outs = jax.vmap(_gated, in_axes=(None, 0, 0, 0))(
        y, e["w_gate"], e["w_up"], e["w_down"])
    return jnp.einsum("te,etd->td", weight, outs, precision=HIGHEST)


def seq_loss(params, row, conf: Dict):
    """Summed cross entropy of one (S+1,) token row."""
    m = dims(conf)
    inp, labels = row[:-1], row[1:]
    s = inp.shape[0]
    x = params["embed"][inp]
    for i in range(m["L"]):
        lp = jax.tree_util.tree_map(lambda t: t[i], params["blocks"])
        a = lp["attn"]
        y = _rms(x, lp["ln1"]["scale"], m["eps"])
        q = mm(y, a["wq"]).reshape(s, m["h"], m["qk"])
        kk = mm(y, a["wk"]).reshape(s, m["h"], m["qk"])
        v = mm(y, a["wv"]).reshape(s, m["h"], m["v"])
        x = x + mm(_attention(q, kk, v), a["wo"])
        y = _rms(x, lp["ln2"]["scale"], m["eps"])
        sh = lp["shared"]
        x = x + _moe(y, lp["moe"], m["k"]) + _gated(
            y, sh["w_gate"], sh["w_up"], sh["w_down"])
    x = _rms(x, params["final_norm"]["scale"], m["eps"])
    return cross_entropy_sum(x, params["lm_head"], labels, m["V"])


def train_ops_per_token(conf: Dict, seq: int) -> Dict[str, float]:
    """``linears``: the attention projections, the ``num_experts_per_tok``
    routed experts a token is sent to and the shared experts; ``other``:
    the head, the router (kept in full precision) and attention."""
    m = dims(conf)
    d, hq, hv = m["d"], m["h"] * m["qk"], m["h"] * m["v"]
    per_layer = (2 * d * hq + 2 * d * hv + m["k"] * 3 * d * m["f"]
                 + 3 * d * m["shared"])
    return {"linears": 6.0 * m["L"] * per_layer,
            "other": 6.0 * d * m["V"] + 6.0 * m["L"] * d * m["E"]
            + attention_ops(m["L"], seq, m["h"], m["qk"], m["v"])}


def tiny(conf: Dict, d: int) -> Dict:
    """``conf`` at width ``d``: 2 layers, 4 heads of d/4 (queries, keys)
    and d/8 (values), 8 experts of d/2, 2 per token, 2 shared."""
    return dict(conf, hidden_size=d, num_hidden_layers=2,
                num_attention_heads=4, qk_head_dim=d // 4,
                v_head_dim=d // 8, n_routed_experts=8, num_experts_per_tok=2,
                n_shared_experts=2, moe_intermediate_size=d // 2,
                vocab_size=4 * d + 44)
