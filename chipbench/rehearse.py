"""Compile a training cell's step for a described TPU v5e chip, without
the chip, and print what the compiler says of its memory.

    JAX_PLATFORMS=cpu python3 -m chipbench.rehearse --workload <name> [--batch 8 12 16] [--reference]

The step is the one the cell runs (same policy, optimizer and donation),
lowered on abstract shapes with the kernels' TPU paths selected.  A batch
fits where arguments plus temporaries stay under 14 GiB of the chip's
15.75 GiB.  This takes tens of seconds a batch, so it is no test.
"""
from __future__ import annotations

import argparse
import os
import sys

from chipbench import registry
from chipbench.run import ROOT

BUDGET = 14 * 2 ** 30


def rehearse(workload: str, batches, reference: bool = False) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import train

    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"        # the kernels' TPU branches
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bench = registry.benchmark()
    cell = registry.workload(bench, workload)
    conf = registry.config(bench, cell["config"])
    mix = registry.traffic(cell["traffic"])
    if reference:
        from chipbench.reference.common import batch_loss
        ref = registry.reference(conf)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        p = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            jax.eval_shape(lambda k: ref.init(k, conf), key))
        b = (batches or [mix["batch"]])[0]
        toks = jax.ShapeDtypeStruct((b, mix["seq"] + 1), jnp.int32,
                                    sharding=one)
        with jax.default_matmul_precision("highest"):
            comp = jax.jit(jax.value_and_grad(lambda pp, t: batch_loss(
                lambda q, row: ref.seq_loss(q, row, conf), pp, t))).lower(
                p, toks).compile()
        ma = comp.memory_analysis()
        print(f"{workload} reference gradient batch={b}: "
              f"args={ma.argument_size_in_bytes / 2**30:.2f}GiB "
              f"temp={ma.temp_size_in_bytes / 2**30:.2f}GiB "
              f"out={ma.output_size_in_bytes / 2**30:.2f}GiB", flush=True)
        return
    for b in batches or [mix["batch"]]:
        with train.program_env(mix):
            _rehearse_batch(workload, conf, dict(mix, batch=b), one)


def _rehearse_batch(workload: str, conf, m, one) -> None:
    """Compile the cell's step at ``m["batch"]`` and print its memory."""
    import jax
    import jax.numpy as jnp

    from chipbench import train

    b = m["batch"]
    _, _, step_fn, make_state = train.program(conf, m)
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    state = jax.tree_util.tree_map(place, jax.eval_shape(make_state, key))
    batch = {"tokens": jax.ShapeDtypeStruct((b, m["seq"] + 1), jnp.int32,
                                            sharding=one)}
    try:
        comp = jax.jit(step_fn, donate_argnums=(0,)).lower(
            state, batch, key).compile()
    except Exception as e:                  # the compiler's refusal
        print(f"{workload} batch={b}: refused: {str(e)[:600]}", flush=True)
        return
    ma = comp.memory_analysis()
    need = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    print(f"{workload} batch={b}: args={ma.argument_size_in_bytes / 2**30:.2f}"
          f"GiB temp={ma.temp_size_in_bytes / 2**30:.2f}GiB "
          f"out={ma.output_size_in_bytes / 2**30:.2f}GiB "
          f"alias={ma.alias_size_in_bytes / 2**30:.2f}GiB "
          f"{'fits' if need <= BUDGET else 'over 14 GiB'}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, nargs="*", default=[])
    ap.add_argument("--reference", action="store_true",
                    help="compile the plain reference's gradient instead")
    args = ap.parse_args(argv)
    rehearse(args.workload, args.batch, args.reference)
    return 0


if __name__ == "__main__":
    sys.exit(main())
