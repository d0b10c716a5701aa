"""Readings from which a training cell's limits are set, on the chip.

    python3 -m chipbench.calibrate --workload <name> --seeds 12 --control 3 --faults 3

For each seed in one process: the program's first steps as the cell runs
them (the lower readings), the program under the cell's control policy (the
nearest precision below the one the cell states, with the limits file's
``control_env`` switches), the program with half of each batch left out,
and the plain reference those are compared with.  Each variant is set up
under its own switches, and the environment is restored after each.  A
state left unchanged reads 1 on ``grad_gap`` and ``change_gap`` by
construction and needs no run.

Prints one JSON line per reading, with ``correct`` as the cell's limits
(``limits/<cell>.json``) judge it, and a summary: the largest sound reading
and the smallest control and fault reading of each number.
"""
from __future__ import annotations

import argparse
import json
import sys

from chipbench import registry
from chipbench.run import ROOT, setup_jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    setup_jax()
    from chipbench import train

    bench = registry.benchmark()
    cell = registry.workload(bench, args.workload)
    conf = registry.config(bench, cell["config"])
    mix = registry.traffic(cell["traffic"])
    lim = registry.limits(args.workload)
    out = open(args.out, "a") if args.out else None
    rows = []

    def emit(row):
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    control = dict(mix, policy=lim["control_policy"],
                   program_env={**mix.get("program_env", {}),
                                **lim.get("control_env", {})})
    cells = {"sound": train.TrainCell(conf, mix, lim),
             "control": train.TrainCell(conf, control, lim),
             "half_batch": train.TrainCell(conf, mix, lim,
                                           fault="half_batch")}
    for c in cells.values():
        c.setup()
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        readings = {}
        for name, c in cells.items():
            if name == "sound" or i < {"control": args.control,
                                       "half_batch": args.faults}[name]:
                c.load(seed)
                readings[name] = c.first_steps()
                c.free()
        ref = cells["sound"].reference()
        for name, r in readings.items():
            numbers = train.compare(r, ref)
            correct, _ = train.verdict(numbers, lim)
            emit(dict(seed=seed, variant=name, correct=correct, **numbers,
                      **train.worst_leaves(r, ref),
                      losses=r.losses, ref_losses=ref.losses))
    return summarize(rows, ("loss_gap", "grad_gap", "change_gap"))


def summarize(rows, numbers) -> int:
    summary = {}
    for k in numbers:
        sound = [r[k] for r in rows if r["variant"] == "sound"]
        other = {v: min(r[k] for r in rows if r["variant"] == v)
                 for v in ("control", "half_batch")
                 if any(r["variant"] == v for r in rows)}
        summary[k] = {"sound_max": max(sound), **{f"{v}_min": x
                                                  for v, x in other.items()}}
    summary["correct"] = {v: sorted({r["correct"] for r in rows
                                     if r["variant"] == v})
                          for v in ("sound", "control", "half_batch")}
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
