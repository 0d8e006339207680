#!/usr/bin/env python3
"""The control of a cell's ``correct``: the reference, put in the program's
place and computed in the precision below the one the configuration states,
has to come out as not correct.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 5

One process, on the chip: set-up once, then for every seed a short window at
the cell's own load and both comparisons on what it returned. Prints, per
seed, every number of the program's sound run and of the control beside its
limit. The benchmark's own runs never call this.
"""

import argparse
import json
import sys

import run


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args()
    cell, config, traffic, _, _ = run.load_cell(args.workload)

    sys.path.insert(0, run.ROOT)
    import jax

    if jax.default_backend() != "tpu" or len(jax.devices()) < cell["chips"]:
        sys.exit("control.py: cell %s needs %d TPU chip(s)" % (cell["name"], cell["chips"]))
    devices = jax.devices()[:cell["chips"]]
    for row in readings(cell, config, traffic, devices,
                        [int(s) for s in args.seeds.split(",")], args.seconds):
        print(json.dumps(row), flush=True)


def readings(cell, config, traffic, devices, seeds, seconds):
    """Per seed: the sound run's numbers and the control's."""
    import program

    program.enable_compile_cache()
    reference = run.load_module("reference", cell["config"] + ".py")
    sweep = run.load_module("configs", cell["config"] + ".py").build(
        config, traffic, seeds[0], devices)
    run.run_sweeps(sweep, seeds[0], indices=range(998, 998 + run.WARMUP_SWEEPS))
    for seed in seeds:
        raws, _, raised = run.run_sweeps(sweep, seed, seconds=seconds)
        records = [r["extract"]() for r in raws]
        row = {"seed": seed, "sweeps": len(raws), "raised": raised}
        for label, control in (("sound", False), ("control", True)):
            numbers = reference.compare(config, traffic, records, seed, control=control)
            row[label] = {name: value for name, value, _ in numbers}
            row[label + "_correct"] = all(v <= lim for _, v, lim in numbers)
        yield row


if __name__ == "__main__":
    main()
