"""The readings that the output check's limits are set from, several
seeds in one process (set-up is most of a run, and its eager model
initialisation is paid once per process):

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 [--what control,half_batch]

For each seed the program is driven through ``engine.run`` to the end of
its warm-up (the window closes as it opens), its first three steps are
held against the reference (``what: program``, the lower readings), and
each variant of ``control.py`` is held against the same reference over
the same rows (the upper readings).  One JSON line per seed and
variant.  Not a benchmark run: no window, no result line.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="control")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--no-program", action="store_true",
                    help="the variants against the reference only")
    args = ap.parse_args(argv)
    from chipbench import checks, control, run, traffic
    from chipbench.clock import FOLLOWED_STEPS, Clock
    c = run.load_cell(args.workload, args.rehearsal)
    cfg, mix, chips = c["config"], c["mix"], c["chips"]
    backend = "cpu" if args.rehearsal else "tpu"
    variants = [w for w in args.what.split(",") if w]
    if not args.no_program:
        import jax
        if (jax.devices()[0].platform != backend
                or len(jax.devices()) != chips):
            print(f"chipbench: {args.workload} needs {chips} {backend} "
                  f"device(s), found {len(jax.devices())} x "
                  f"{jax.devices()[0].platform}", file=sys.stderr)
            return 3
    for seed in [int(s) for s in args.seeds.split(",")]:
        sides = {}
        if not args.no_program:
            clock = Clock(0.0, int(c["cell_file"]["warmup_steps"]),
                          c["program"].optimizer_memory)
            if run.drive(run.program_flags(c, seed, backend, False),
                         clock) is None:
                return 4
            sides["program"] = checks.program_side(
                clock.captured, c["program"], cfg)
            del clock
            gc.collect()
        batches = traffic.batches(
            mix, seed, cfg["image_size"], cfg["num_classes"], chips,
            FOLLOWED_STEPS, workers=min(int(mix["workers"]), 16))
        ref = checks.reference_side(
            c["reference"].follow(cfg, seed, batches, replicas=chips))
        for what in variants:
            sides[what] = control.variant(c["reference"], c["program"],
                                          cfg, seed, batches, chips, what)
        for what, side in sides.items():
            numbers = checks.compare(side, ref)
            numbers.pop("_where")
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "what": what, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
