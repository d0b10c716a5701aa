"""Record the small scoped trace ``test_scopes.py`` reads, on one TPU chip.

    python3 -m chipbench.tests.data.record_scoped_trace <outdir>

Three steps of a jitted train step in the program's own names: a layer
scan (``layers``) whose body ``jax.checkpoint`` recomputes in the
backward, each layer a norm (``norm``) and the program's int8 linear
(its policy's ``custom_vjp`` on the int8 Pallas kernels, forward and both
backward matmuls, under ``linear`` and ``qdq``), a loss (``head``) and a
momentum update (``optimizer``), inside the benchmark's spans, with a
20 ms host sleep in each ``chipbench.wait`` span (so the device idles
between programs).  Writes ``tiny_scoped.xplane.pb`` and the compiled program's
text ``tiny_scoped_hlo.txt``.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def build():
    """The train step and its arguments (weights, momentum, batch)."""
    import jax
    import jax.numpy as jnp

    from repro.core import parse_policy
    from repro.core.qpolicy import LinearCtx

    policy = parse_policy("*=w8c+a8t+g8t@int8_pallas")

    def loss(ws, x):
        def body(h, w):
            with jax.named_scope("norm"):
                n = h * jax.lax.rsqrt(jnp.mean(jnp.square(
                    h.astype(jnp.float32)), -1, keepdims=True) + 1e-6
                ).astype(h.dtype)
            return h + policy.linear(LinearCtx("mlp_up"), n, w), None

        with jax.named_scope("layers"):
            h, _ = jax.lax.scan(jax.checkpoint(body), x, ws)
        with jax.named_scope("head"):
            return jnp.mean(jnp.square(h.astype(jnp.float32)))

    def step(ws, m, x):
        val, g = jax.value_and_grad(loss)(ws, x)
        with jax.named_scope("optimizer"):
            m = 0.9 * m + g.astype(jnp.float32)
            ws = (ws.astype(jnp.float32) - 1e-3 * m).astype(ws.dtype)
        return ws, m, val

    k = jax.random.split(jax.random.PRNGKey(0), 2)
    ws = (0.05 * jax.random.normal(k[0], (4, 512, 512))).astype(jnp.bfloat16)
    m = jnp.zeros(ws.shape, jnp.float32)
    x = jax.random.normal(k[1], (1024, 512)).astype(jnp.bfloat16)
    return step, (ws, m, x)


def main(out: str) -> int:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    sys.path.insert(0, os.path.join(root, "src"))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 2
    step, (ws, m, x) = build()
    comp = jax.jit(step).lower(ws, m, x).compile()
    jax.block_until_ready(comp(ws, m, x))
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.train_step"):
                ws, m, val = comp(ws, m, x)
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                val.block_until_ready()
                time.sleep(0.02)
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    src = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(src[0], os.path.join(out, "tiny_scoped.xplane.pb"))
    with open(os.path.join(out, "tiny_scoped_hlo.txt"), "w") as fh:
        fh.write(comp.as_text())
    shutil.rmtree(tdir)
    print("recorded",
          os.path.getsize(os.path.join(out, "tiny_scoped.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
