"""Read the compared numbers of one cell over many seeds, for setting its
limits: the program's sound runs, and on the first seeds the control (the
reference itself in bfloat16, in the program's place) and the planted
faults (half of each batch left out; the exchange left out), each against
the float32 reference. The first steps need no measured window.

    python bench/calibrate.py --workload <name> --seeds 12 --faults 3 \
        [--out calibrate.<name>.jsonl]

One JSON line per seed and side: {"seed", "side", "numbers"}. A state left
unchanged reads 1 on ``update_gap`` by its definition and needs no run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3,
                    help="on how many of the seeds to read the control "
                         "and the faults")
    ap.add_argument("--first-seed", type=int, default=5_000_000_011)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from bench import check, common, drivers
    cell = common.resolve_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    spec = cell["cell"]
    steps = int(spec["check_steps"])
    out = open(args.out, "a") if args.out else None

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t = time.perf_counter()
        d = drivers.load(spec["driver"])(cell["config"], cell["traffic"],
                                         spec, seed, devices[:cell["chips"]])
        d.setup()
        prog = d.readings(steps)
        d.free()
        ref = d.reference(steps)
        emit({"seed": seed, "side": "program",
              "numbers": check.numbers(prog, ref),
              "seconds": time.perf_counter() - t})
        if i < args.faults:
            for side, kw in (("control_bf16", {"dtype": jnp.bfloat16}),
                             ("half_batch", {"fault": "half_batch"}),
                             ("no_exchange", {"fault": "no_exchange"})):
                t = time.perf_counter()
                emit({"seed": seed, "side": side,
                      "numbers": check.numbers(d.reference(steps, **kw), ref),
                      "seconds": time.perf_counter() - t})
        del d
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
