"""Compile-only depth probe of a cell's configuration on one chip.

    python3 bench/depth.py --workload <cell> --depths 4,5

For each depth, builds the cell's trainer at that many layers and compiles
its step for the chip, and prints the compiled program's peak
(``memory_analysis``: arguments + outputs - aliased + temporaries + code)
against 85% of the chip's ``bytes_limit``.  The configuration file keeps
the deepest depth that fits; no run searches for it.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import program, spec, traffic  # noqa: E402

SHARE = 0.85


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--depths", required=True)
    args = ap.parse_args(argv)
    import jax

    cell = spec.load_cell(args.workload)
    device = jax.devices()[0]
    budget = SHARE * device.memory_stats()["bytes_limit"]
    batch = next(traffic.agent_batches(cell.traffic,
                                       cell.config["vocab_size"], 0))
    for n in [int(d) for d in args.depths.split(",")]:
        at = dataclasses.replace(cell, config={**cell.config, "n_layers": n})
        t0 = time.perf_counter()
        trainer = program.build(at, 0)
        compiled = trainer._step_fn.lower(trainer.state.params,
                                          trainer.state.opt_state,
                                          batch).compile()
        m = compiled.memory_analysis()
        peak = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes
                + m.generated_code_size_in_bytes)
        print(json.dumps({"workload": cell.name, "n_layers": n,
                          "compiled_peak_bytes": peak, "budget_bytes": budget,
                          "bytes_limit": device.memory_stats()["bytes_limit"],
                          "fits": peak <= budget,
                          "seconds": time.perf_counter() - t0}), flush=True)
        del trainer, compiled
    return 0


if __name__ == "__main__":
    sys.exit(main())
