"""Readings that set a cell's limits: the program's over many seeds, and
the control's and the faults' over a few.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        [--program] [--control] [--faults half_batch,no_exchange,altered] \
        [--upper-seeds 3]

For each seed the plain reference follows the cell's first steps once.
Then, against it:

``--program``  the program, driven through the same first steps as a run
               drives it (one trainer for all seeds, restarted from each
               seed's weights): the lower readings;
``--control``  the reference computed with float8 (e4m3) operands in every
               contraction, the precision below the configuration's
               bfloat16, put in the program's place: an upper reading;
``--faults``   the reference with a fault planted, put in the program's
               place: the other upper readings.  A step that returns its
               state unchanged reads 1 by construction and is not run.

The control and the faults run on the first ``--upper-seeds`` seeds only
(all of them by default).

Prints one JSON line per reading.  Runs on one chip at the cell's own
size; ``bench/tests`` runs it on the CPU at a small one.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import nn, program, reference, spec, traffic  # noqa: E402


def first_batches(cell, seed: int):
    feed = traffic.agent_batches(cell.traffic, cell.config["vocab_size"], seed)
    return [next(feed) for _ in range(cell.traffic["check_steps"])]


def reference_readings(cell, seed: int, cast=nn.exact, fault=None):
    t = cell.traffic
    return reference.run(cell.reference, cell.config, first_batches(cell, seed),
                         seed, traffic.TOPOLOGIES[t["topology"]](t["agents"]),
                         t["lr"], t["momentum"], cast, fault)


def emit(cell, what: str, seed: int, got: dict, want: dict, seconds: float):
    compared = reference.compare(got, want)
    print(json.dumps({"workload": cell.name, "reading": what, "seed": seed,
                      "seconds": seconds,
                      **{k: v for k, (v, _) in compared.items()},
                      "worst": {k: w for k, (_, w) in compared.items()}}),
          flush=True)
    return compared


def main(argv=None, root=spec.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    ap.add_argument("--upper-seeds", type=int, default=0)
    args = ap.parse_args(argv)
    import run

    cell = spec.load_cell(args.workload, root)
    run.enable_cache(root)
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    want = {}
    upper = ([("control", nn.fp8, None)] if args.control else []) + [
        (f, nn.exact, f) for f in faults]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        want[seed] = reference_readings(cell, seed)
        print(json.dumps({"workload": cell.name, "reading": "reference",
                          "seed": seed, "seconds": time.perf_counter() - t0,
                          "losses": want[seed]["losses"]}), flush=True)
        for what, cast, fault in (upper if i < (args.upper_seeds or len(seeds))
                                  else []):
            t0 = time.perf_counter()
            got = reference_readings(cell, seed, cast, fault)
            emit(cell, what, seed, got, want[seed], time.perf_counter() - t0)
    if args.program:
        trainer = program.build(cell, seeds[0])
        for seed in seeds:
            t0 = time.perf_counter()
            program.set_weights(trainer, cell, seed)
            got = program.first_steps(trainer, cell, first_batches(cell, seed),
                                      seed)
            emit(cell, "program", seed, got, want[seed],
                 time.perf_counter() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
