#!/usr/bin/env python3
"""Read a cell's check numbers over many seeds in one process: the program's
(the lower readings a limit is set from) and the control's (the upper).

    python3 perf/control.py --workload <name> --seeds 1,2,3 [--control-seeds 1,2]
                            [--calls 2] [--rehearse-cpu]

For each seed: the data from the seed, the driver's set-up, ``--calls`` calls
of every shape of the mix through the timed entry, then the same judge that
``perf/run.py`` uses.  For each control seed the driver's ``control`` makes
the results in the program's place.  Every reading is held to the workload's
limits and printed as one JSON line with its ``correct``; the last line says
whether the two separated (the program correct on every seed, the control on
none), and the exit code is 1 where they did not.  Not run by the benchmark's
own runs.
"""

import argparse
import gc
import json
import os
import sys

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf import manifest as mf  # noqa: E402
from perf import run  # noqa: E402
from perf import traffic as tf  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--reference-control", action="store_true",
                    help="the reference one precision lower, also where the "
                         "workload names a path of the program as its control")
    ap.add_argument("--control-operands", default="",
                    help="override check.control_operands (e.g. bfloat16: the "
                         "witness that should read like the program)")
    args = ap.parse_args(argv)
    cell = mf.load_cell(mf.load_manifest(), args.workload, args.rehearse_cpu)
    config, wl, chips = cell["config"], cell["workload"], int(cell["cell"]["chips"])
    if args.control_operands:
        wl["check"]["control_operands"] = args.control_operands
    if args.reference_control:
        wl["check"].pop("control", None)
    if args.rehearse_cpu:
        run.rehearsal_env(chips)

    import jax

    if not args.rehearse_cpu and jax.devices()[0].platform != "tpu":
        sys.exit("perf/control.py needs a TPU (or --rehearse-cpu)")
    import heat_tpu as ht
    from heat_tpu.utils import compile_cache

    compile_cache.enable()
    limits = wl["check"]["limits"]
    verdicts = {"program": [], "control": []}
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds:
        driver, state, ctx = run.build(jax, ht, cell, seed)
        stream = tf.calls(wl["traffic"], seed)
        items = [next(stream) for _ in range(args.calls * len(tf.shapes(wl["traffic"])))]
        for who in ("program", "control"):
            if who == "control" and seed not in control_seeds:
                continue
            kept = []
            for item in items:
                if who == "program":
                    out = driver.call(state, item)
                else:
                    out = driver.control(state, item, ctx)
                run.ready(jax, out)
                gc.collect()
                kept.append(driver.keep(state, item, out))
                if wl["check"].get("sample", {}).get("policy") == "last":
                    kept = kept[-1:]
                out = None
            numbers, info = driver.check(state, kept, ctx)
            del kept
            over = sorted(k for k, v in numbers.items() if not v <= limits[k])
            verdicts[who].append(not over)
            print(json.dumps({"workload": args.workload, "seed": seed, "who": who,
                              "correct": not over, "over": over, "numbers": numbers,
                              "limits": limits, "info": info}), flush=True)
        del state, ctx
        gc.collect()
    # the program has to pass every limit on every seed, and the control has
    # to fail one on every seed it was read on
    sound = all(verdicts["program"]) and not any(verdicts["control"])
    print(json.dumps({"workload": args.workload, "separated": sound,
                      "program_correct": f"{sum(verdicts['program'])}/{len(verdicts['program'])}",
                      "control_correct": f"{sum(verdicts['control'])}/{len(verdicts['control'])}"}),
          flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
