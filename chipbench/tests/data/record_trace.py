"""Record the small trace ``test_trace.py`` reads, on one TPU chip.

    python3 -m chipbench.tests.data.record_trace <outdir>

Three steps of a jitted function that runs the program's int8 matmul
Pallas kernel and an XLA reduction, inside the benchmark's spans, with a
20 ms host sleep in each ``chipbench.wait`` span (so the device idles
there).  Writes ``tiny.xplane.pb`` and the compiled program's text
``tiny_hlo.txt``.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    import jax.numpy as jnp

    from repro.kernels.int8_matmul import int8_matmul

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.randint(k[0], (512, 512), -127, 127).astype(jnp.int8)
    w = jax.random.randint(k[1], (512, 256), -127, 127).astype(jnp.int8)
    rs = jnp.full((512, 1), 0.01, jnp.float32)
    cs = jnp.full((1, 256), 0.02, jnp.float32)
    f = jax.jit(lambda a, b, r, c: jnp.sum(
        int8_matmul(a, b, r, c).astype(jnp.float32) ** 2))
    comp = f.lower(x, w, rs, cs).compile()
    comp(x, w, rs, cs).block_until_ready()
    tdir = tempfile.mkdtemp()
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.train_step"):
                y = comp(x, w, rs, cs)
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                y.block_until_ready()
                time.sleep(0.02)
    jax.profiler.stop_trace()
    os.makedirs(out, exist_ok=True)
    src = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(src[0], os.path.join(out, "tiny.xplane.pb"))
    with open(os.path.join(out, "tiny_hlo.txt"), "w") as fh:
        fh.write(comp.as_text())
    shutil.rmtree(tdir)
    print("recorded", os.path.getsize(os.path.join(out, "tiny.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
