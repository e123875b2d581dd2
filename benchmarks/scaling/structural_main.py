# CPU-STRUCTURAL TOOL: every leg is a child process forced onto the CPU
# (JAX_PLATFORMS=cpu, virtual devices), and this parent never touches JAX,
# so it neither needs nor holds a chip.  It counts collectives and bytes
# in compiled programs; it measures no device time.
#
# Structural-census sweep + scaling-law verdicts (round 5; VERDICT r4 #1).
#
# Runs structural.py at mesh sizes 2/4/8 (each in a subprocess: the forced
# device count is fixed at jax import) and ASSERTS each workload's wire law:
#
#   law "count_mesh_invariant":  collective instruction counts identical at
#       2/4/8 devices — the program's structure does not degrade with scale.
#       (sort_network is the deliberate exception: its round count GROWS
#       with the mesh, which is exactly why columnsort exists; the law for
#       it is count_grows_with_mesh.)
#   law "bytes_linear_in_n":     per-device collective bytes double when the
#       problem doubles (columnsort, mask-select, MoE, resplit, ring cdist).
#   law "bytes_invariant_in_n":  TSQR's all-gather carries S k-by-k R
#       panels — independent of the row count.
#   law "per_device_bytes_strong": at fixed n, per-device bytes halve as the
#       mesh doubles (the collective moves 1/D of the volume per chip).
#   law "per_device_bytes_grow":  TSQR's gather output is S*k^2 per device —
#       it GROWS linearly with the mesh (the known TSQR tree tradeoff; at
#       pod scale this is the term that caps S).
#   law "local_expected":        replicated-operand matmuls compile to ZERO
#       collectives — an asserted-empty census, not a missing one.
#
# Output: one JSON doc (the SCALING_r05 structural section) where every
# workload row either differs meaningfully across legs or is asserted
# invariant — and every law carries an ok flag the suite fails on.
import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

LIN = (1.7, 2.3)      # tolerance for "doubles" (padding skews small shapes)
HALF = (0.42, 0.58)   # tolerance for "halves"


def run_leg(devices: int, base_n: int) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        + env.get("XLA_FLAGS", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(HERE))
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "structural.py"),
         "--devices", str(devices), "--base-n", str(base_n)],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    if out.returncode != 0:
        raise RuntimeError(f"leg D={devices} failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def total_bytes(census: dict, kinds=None) -> int:
    return sum(
        v["bytes_out"] for k, v in census.items() if kinds is None or k in kinds
    )


def counts(census: dict) -> dict:
    return {k: v["count"] for k, v in census.items()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--base-n", type=int, default=24576)  # divisible by 64:
    # per-shard counts stay exact at D=2/4/8 so census counts are comparable
    ap.add_argument("--devices", default="2,4,8")
    args = ap.parse_args()
    sizes = [int(s) for s in args.devices.split(",")]

    legs = {d: run_leg(d, args.base_n) for d in sizes}
    for d in sizes:
        print(f"leg D={d} done", file=sys.stderr)

    wl_names = list(legs[sizes[0]]["scales"]["n1"]["workloads"])

    def hlo(d, scale, wl):
        return legs[d]["scales"][scale]["workloads"][wl]["hlo"]

    laws = []

    def law(workload, name, observed, ok):
        laws.append({
            "workload": workload, "law": name,
            "observed": observed, "ok": bool(ok),
        })

    for wl in wl_names:
        cts = {d: counts(hlo(d, "n1", wl)) for d in sizes}
        if wl == "columnsort":
            # the O(n) claim lives in the all-to-all count (2 deal steps x
            # 3 carried arrays); the merge-split cleanup is a fixed 3-round
            # schedule whose ppermutes are BOUNDED (<= 9) — a parity round
            # with no partners at small S compiles away, so the count may
            # shrink below 9 but must never grow with the mesh
            a2a_inv = len({c.get("all-to-all") for c in cts.values()}) == 1
            pp = [cts[d].get("collective-permute", 0) for d in sizes]
            bounded = all(p <= 9 for p in pp) and all(
                pp[i] <= pp[i + 1] or pp[i + 1] == pp[-1]
                for i in range(len(pp) - 1)
            ) and pp[-2] == pp[-1]
            law(wl, "a2a_count_mesh_invariant_cleanup_bounded", cts,
                a2a_inv and bounded)
        elif wl == "sort_network":
            # the odd-even network's ppermute rounds grow with the mesh —
            # the anti-pattern columnsort replaces
            grows = all(
                cts[sizes[i]].get("collective-permute", 0)
                < cts[sizes[i + 1]].get("collective-permute", 0)
                for i in range(len(sizes) - 1)
            )
            law(wl, "count_grows_with_mesh", cts, grows)
        else:
            invariant = len({json.dumps(c, sort_keys=True) for c in cts.values()}) == 1
            law(wl, "count_mesh_invariant", cts, invariant)

    # exact structural counts (the claims the docstrings/tests make)
    d0 = sizes[-1]
    law("columnsort", "two_all_to_all_steps_x3_arrays",
        counts(hlo(d0, "n1", "columnsort")),
        counts(hlo(d0, "n1", "columnsort")).get("all-to-all") == 6)
    law("tsqr", "one_all_gather",
        counts(hlo(d0, "n1", "tsqr")),
        counts(hlo(d0, "n1", "tsqr")).get("all-gather") == 1)
    law("mask_select", "one_reduce_scatter_plus_count_exchange",
        counts(hlo(d0, "n1", "mask_select")),
        counts(hlo(d0, "n1", "mask_select")).get("reduce-scatter") == 1)
    law("int_gather", "one_reduce_scatter_of_output_volume",
        counts(hlo(d0, "n1", "int_gather")),
        counts(hlo(d0, "n1", "int_gather")) == {"reduce-scatter": 1})
    law("tiled_gather", "one_reduce_scatter_in_tile_loop",
        counts(hlo(d0, "n1", "tiled_gather")),
        counts(hlo(d0, "n1", "tiled_gather")) == {"reduce-scatter": 1})
    law("tiled_resplit", "one_all_to_all_in_tile_loop",
        counts(hlo(d0, "n1", "tiled_resplit")),
        counts(hlo(d0, "n1", "tiled_resplit")).get("all-to-all") == 1)
    law("moe_dispatch", "two_all_to_alls",
        counts(hlo(d0, "n1", "moe_dispatch")),
        counts(hlo(d0, "n1", "moe_dispatch")).get("all-to-all") == 2)
    law("resplit_0to1", "one_all_to_all",
        counts(hlo(d0, "n1", "resplit_0to1")),
        counts(hlo(d0, "n1", "resplit_0to1")).get("all-to-all") == 1)
    for wl in ("matmul_s0None", "matmul_sNone1"):
        law(wl, "local_expected", counts(hlo(d0, "n1", wl)),
            hlo(d0, "n1", wl) == {})
    law("matmul_s10", "inner_split_is_all_reduce",
        counts(hlo(d0, "n1", "matmul_s10")),
        counts(hlo(d0, "n1", "matmul_s10")).get("all-reduce") == 1)

    # bytes vs n at the largest mesh
    linear_wls = {
        "columnsort": ("all-to-all",),
        "sort_network": ("collective-permute",),
        "mask_select": ("reduce-scatter",),
        "int_gather": ("reduce-scatter",),
        "moe_dispatch": ("all-to-all",),
        "resplit_0to1": ("all-to-all",),
        "ring_cdist": ("collective-permute",),
    }
    for wl, kinds in linear_wls.items():
        b1 = total_bytes(hlo(d0, "n1", wl), kinds)
        b2 = total_bytes(hlo(d0, "n2", wl), kinds)
        r = b2 / b1 if b1 else None
        law(wl, "bytes_linear_in_n", {"n1": b1, "n2": b2, "ratio": r},
            r is not None and LIN[0] <= r <= LIN[1])
    tb = {s: total_bytes(hlo(d0, s, "tsqr"), ("all-gather",)) for s in ("n1", "n2")}
    law("tsqr", "bytes_invariant_in_n", tb, tb["n1"] == tb["n2"] > 0)

    # per-device bytes vs mesh size at fixed n
    strong_wls = {
        "columnsort": ("all-to-all",),
        "mask_select": ("reduce-scatter",),
        "int_gather": ("reduce-scatter",),
        "resplit_0to1": ("all-to-all",),
        "ring_cdist": ("collective-permute",),
        "moe_dispatch": ("all-to-all",),
    }
    for wl, kinds in strong_wls.items():
        by_d = {d: total_bytes(hlo(d, "n1", wl), kinds) for d in sizes}
        ratios = [
            by_d[sizes[i + 1]] / by_d[sizes[i]]
            for i in range(len(sizes) - 1)
            if by_d[sizes[i]]
        ]
        ok = bool(ratios) and all(HALF[0] <= r <= HALF[1] for r in ratios)
        law(wl, "per_device_bytes_strong", by_d, ok)
    tsqr_by_d = {d: total_bytes(hlo(d, "n1", "tsqr"), ("all-gather",)) for d in sizes}
    tsqr_ratios = [
        tsqr_by_d[sizes[i + 1]] / tsqr_by_d[sizes[i]]
        for i in range(len(sizes) - 1)
    ]
    law("tsqr", "per_device_bytes_grow_with_mesh", tsqr_by_d,
        all(LIN[0] <= r <= LIN[1] for r in tsqr_ratios))

    # tiled-transport laws (round 6, parallel/transport.py): per-instruction
    # collective bytes capped by the ABSOLUTE tile budget while total wire
    # (n_tiles x bytes_out) still equals the monolithic volume, at meshes
    # 4 AND 8 and both problem sizes — the O(N/S + tile) staging claim
    def wl_meta(d, scale, wl):
        return legs[d]["scales"][scale]["workloads"][wl]["meta"]

    tiled_wls = {"tiled_gather": "reduce-scatter", "tiled_resplit": "all-to-all"}
    for wl, kind in tiled_wls.items():
        mono_key = "mono_bytes" if wl == "tiled_gather" else "slab_bytes"
        obs, ok = {}, True
        for d in [s for s in sizes if s in (4, 8)]:
            for scale in ("n1", "n2"):
                m = wl_meta(d, scale, wl)
                b = hlo(d, scale, wl)[kind]["bytes_out"]
                wire = m["n_tiles"] * b
                mono = m[mono_key]
                obs[f"D{d}/{scale}"] = {
                    "n_tiles": m["n_tiles"], "instr_bytes": b, "wire": wire,
                    "mono": mono,
                }
                ok = ok and (
                    m["n_tiles"] > 1          # the loop actually tiles
                    and b <= m["tile_budget"]  # each instruction in budget
                    and mono <= wire < mono + b  # wire volume preserved
                )
        law(wl, "instr_bytes_budget_capped_wire_preserved", obs, ok)
        # per-device WIRE still halves as the mesh doubles (4 -> 8): the
        # budget caps the instruction, not the physics
        if 4 in sizes and 8 in sizes:
            w = {
                d: wl_meta(d, "n1", wl)["n_tiles"]
                * hlo(d, "n1", wl)[kind]["bytes_out"]
                for d in (4, 8)
            }
            r = w[8] / w[4] if w[4] else None
            law(wl, "per_device_wire_strong", w,
                r is not None and HALF[0] <= r <= HALF[1])

    # matmul: counts AND bytes mesh-invariant (GSPMD re-chooses nothing)
    for wl in [w for w in wl_names if w.startswith("matmul_s")]:
        by_d = {d: hlo(d, "n1", wl) for d in sizes}
        invariant = len({json.dumps(c, sort_keys=True) for c in by_d.values()}) == 1
        law(wl, "census_mesh_invariant", {str(d): counts(c) for d, c in by_d.items()},
            invariant)

    all_ok = all(l["ok"] for l in laws)
    empty = [
        f"{wl}@D={d}" for d in sizes for wl in wl_names
        if hlo(d, "n1", wl) == {} and not wl.endswith(("s0None", "sNone1"))
    ]
    doc = {
        "suite": "structural-census",
        "note": "compile-only HLO census of the framework's data-volume "
                "collective programs; bytes_out = per-participant output "
                "buffer; loop-carried collectives count once (structure, "
                "not trip count)",
        "base_n": args.base_n,
        "legs": legs,
        "laws": laws,
        "laws_all_ok": all_ok,
        "unexpected_empty_censuses": empty,
    }
    print(json.dumps({"laws": laws, "laws_all_ok": all_ok,
                      "unexpected_empty_censuses": empty}, indent=1))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1)
    if not all_ok or empty:
        sys.exit(1)


if __name__ == "__main__":
    main()
