"""Run one cell of BENCHMARK.json once on the chips of this machine.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each number compared for
``correct`` beside its limit.  The same numbers close standard error.

There is no fallback: where JAX finds no TPU, or fewer chips than the cell
asks for, the run prints no result and exits with code 2.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
#: JAX's persistent compilation cache: a fixed directory inside the checkout
CACHE_DIR = ROOT / ".jax_cache"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str, code: int = 2) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def setup_jax() -> None:
    """Compilation cache at its fixed path (``JAX_COMPILATION_CACHE_DIR``
    wins where set), holding every program, however quick to compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(devs, chips: int) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def result_line(res: dict, metrics: dict, device: dict,
                breakdown=None) -> str:
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = res["checks"]
    return json.dumps(out)


def execute(args, bench: dict, cell: dict, conf: dict, mix: dict,
            lim: dict, devs, fault=None) -> str:
    """Everything of a run after the look for chips: set up, measure,
    check, and return the result line (the checks go to standard error).
    ``fault`` plants a fault in the timed path, for the benchmark's tests."""
    from chipbench import registry
    from chipbench import trace as cbtrace
    driver = importlib.import_module(f"chipbench.{mix['kind']}")
    watch = cbtrace.Window(bool(args.trace))
    res = driver.run_cell(conf, mix, lim, args.seed, args.seconds,
                          watch, fault=fault)
    print(f"traces and compiles inside the window: {watch.compiles}",
          flush=True)
    device = device_info(devs, cell["chips"])
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    breakdown = None
    if args.trace:
        red = watch.reduce()
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        ctx = cbtrace.Context(red, conf, mix, res, devs[0].device_kind)
        metrics = {}
        for m in registry.metrics_for(bench, args.workload, "per_layer"):
            v = registry.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        watch.cleanup()
    else:
        setup_s = res["setup_end"] - _T0
        values = dict(res["e2e"], setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in registry.metrics_for(bench, args.workload,
                                                 "end_to_end")}
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result_line(res, metrics, device, breakdown)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import registry
    try:
        bench = registry.benchmark()
        cell = registry.workload(bench, args.workload)
        conf = registry.config(bench, cell["config"])
        mix = registry.traffic(cell["traffic"])
        lim = registry.limits(args.workload)
    except registry.BenchError as e:
        return fail(str(e))

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return fail(f"no TPU: JAX found {devs[0].platform}; nothing was run")
    if len(devs) < cell["chips"]:
        return fail(f"{args.workload} needs {cell['chips']} chips, JAX "
                    f"found {len(devs)}; nothing was run")
    setup_jax()
    try:
        importlib.import_module("repro")
    except ImportError as e:
        return fail(f"the program under test is not in src/: {e}")

    print(execute(args, bench, cell, conf, mix, lim, devs), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
