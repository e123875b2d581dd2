# CPU-STRUCTURAL TOOL: every leg is a child process forced onto the CPU
# (JAX_PLATFORMS=cpu, virtual devices), and this parent never touches JAX,
# so it neither needs nor holds a chip.  Nothing it prints is a device time.
#
# Weak/strong scaling sweep over virtual mesh sizes 1/2/4/8 (reference:
# benchmarks/2020/*/config.json; round-3 VERDICT missing #6).  Each mesh
# size runs in a SUBPROCESS with its own forced device count; results
# merge into one JSON document with derived efficiencies.
#
# Caveat, stated in the artifact: the virtual devices share one host's
# cores, so absolute speedups are bounded by real parallelism — the
# signal is the scaling TREND of the sharded compute+collective
# structure (the only multi-chip perf signal this environment can
# produce), not hardware speedup.
import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def run_leg(devices: int, mode: str, base_n: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run_one.py"),
         "--devices", str(devices), "--mode", mode, "--base-n", str(base_n)],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    if out.returncode != 0:
        raise RuntimeError(f"leg {devices}/{mode} failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--base-n", type=int, default=200_000)
    ap.add_argument("--devices", default="1,2,4,8")
    ap.add_argument("--modes", default="strong,weak",
                    help="comma-separated subset (a full sweep can exceed a"
                         " driver window; merge part files by hand)")
    ap.add_argument("--merge", nargs="*", default=None,
                    help="previously saved leg JSON-lines files to fold in")
    args = ap.parse_args()
    sizes = [int(s) for s in args.devices.split(",")]

    legs = []
    if args.merge:
        for f in args.merge:
            with open(f) as fh:
                legs.extend(json.loads(l) for l in fh if l.strip())
    for mode in [m.strip() for m in args.modes.split(",") if m.strip()]:
        for d in sizes:
            leg = run_leg(d, mode, args.base_n)
            print(json.dumps(leg), file=sys.stderr)
            legs.append(leg)
            if args.out:
                with open(args.out + ".legs", "a") as fh:
                    fh.write(json.dumps(leg) + "\n")

    def eff(mode, metric):
        mode_legs = [l for l in legs if l["mode"] == mode]
        if not mode_legs:
            return {}
        base_dev = min(l["devices"] for l in mode_legs)
        base = next(
            l for l in mode_legs if l["devices"] == base_dev
        )["results"][metric]
        out = {}
        for l in mode_legs:
            t = l["results"][metric]
            if mode == "strong":
                out[l["devices"]] = round(base / t, 3)   # speedup
            else:
                out[l["devices"]] = round(base / t, 3)   # efficiency (t const ideal)
        return out

    metrics = list(legs[0]["results"])

    # collective-census analysis (round-4: the structural signal). For each
    # workload: per-mesh-size collective counts must be mesh-size-INVARIANT
    # (the program's structure does not degrade as devices grow), and
    # per-device bytes x devices gives the total-wire-vs-devices trend.
    census_ok = True
    census_summary = {}
    for mode in {l["mode"] for l in legs}:
        mode_legs = sorted(
            (l for l in legs if l["mode"] == mode and l.get("collective_census")),
            key=lambda l: l["devices"],
        )
        multi = [l for l in mode_legs if l["devices"] > 1]
        if not multi:
            continue
        for wl in multi[0]["collective_census"]:
            counts = {
                l["devices"]: {
                    k: v["count"] for k, v in l["collective_census"][wl].items()
                }
                for l in multi
            }
            wire = {
                l["devices"]: sum(
                    v["bytes_out"] for v in l["collective_census"][wl].values()
                ) * l["devices"]
                for l in multi
            }
            invariant = len({json.dumps(c, sort_keys=True) for c in counts.values()}) == 1
            census_ok = census_ok and invariant
            census_summary[f"{mode}:{wl}"] = {
                "counts_by_devices": counts,
                "count_mesh_invariant": invariant,
                "total_wire_bytes_by_devices": wire,
            }

    doc = {
        "suite": "scaling-2020",
        "note": "virtual CPU mesh: same host cores for every leg; wall times"
                " are secondary — the collective census (counts x bytes per"
                " compiled program) is the structural multi-chip signal",
        "legs": legs,
        "strong_speedup": {m: eff("strong", m) for m in metrics},
        "weak_efficiency": {m: eff("weak", m) for m in metrics},
        "census_summary": census_summary,
        # null (not true) when no multi-device census legs existed: an
        # unchecked invariant must not read as a verified one
        "census_counts_mesh_invariant": census_ok if census_summary else None,
    }
    print(json.dumps(doc))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1)


if __name__ == "__main__":
    main()
