"""Perf-regression harness over a BENCH_cb_r*.json trajectory.

cb round documents (``BENCH_cb_rNN.json`` at the repo root) are the
suite's performance memory.  None is checked in today — the earlier
rounds were taken through a remote plug-in that no longer exists and
were removed — so every row reports ``no-history`` until ROADMAP S0
records the first round on the chip.  The machinery:

* :func:`load_rounds` reads every checked-in round document,
* :func:`best_history` reduces them to the best (minimum) ``wall_s``
  per row name — compared **backend-to-backend only** (a CPU smoke run
  is never judged against the TPU trajectory; such rows report
  ``no-history`` and pass, keeping the gate honest rather than vacuously
  red on dev machines),
* :func:`compare` judges a current measurement list row-by-row against
  that best, with a per-row noise tolerance,
* :func:`check` attaches the delta table to a cb suite document
  (``doc["regression"]``) and returns the out-of-tolerance rows —
  ``main.py --check-regression`` exits nonzero on any,
* :func:`self_check` replays the gate on the trajectory itself (latest
  round vs the best of the earlier ones) so CI proves the harness bites
  without needing TPU hardware.

Tolerance model: a row regresses when ``wall_s`` exceeds
``max(best * (1 + tol), best + ABS_FLOOR_S)``.  The absolute floor keeps
sub-millisecond rows (dispatch-latency dominated) from flagging on
scheduler jitter; the relative tolerance covers
real kernels.  Rows whose checked-in notes document larger spreads carry
explicit entries in :data:`TOLERANCE` — each one cites its source."""

import argparse
import glob
import json
import os
import re
import sys

# default relative tolerance: a real kernel may not lose more than 25%
# against its best checked-in round
DEFAULT_REL_TOL = 0.25
# absolute jitter floor: deltas under 2 ms never flag (dispatch latency
# noise on tiny rows)
ABS_FLOOR_S = 0.002

# Per-row overrides, each justified by the row's own checked-in metadata:
TOLERANCE = {
    # 50 dependent tiny steps: the spread is dispatch jitter, not
    # kernel time (10-50 ms across runs of one commit)
    "lanczos": 3.0,
    # single-run whole-`.fit` walls including the estimator's
    # n_iter/inertia host readbacks (their notes say so) — not
    # slope-measured, so host scheduling rides the number
    "kmeans": 0.4,
    "kmedians": 0.4,
    "kmedoids": 0.4,
    # single-run with one deliberate host sync (qr.py breakdown check)
    "tsqr_user_call": 0.4,
    # round-15 kernel-tier rows: each is measured from a COLD tuning
    # table (kernels.py clears it), so the timed region includes the
    # explore phase running BOTH arms back to back — their notes record
    # the measured arm choice, and the wall rides which arm won and how
    # quickly the table resolved
    "qr_panel_fused": 0.5,
    "lasso_sweep_fused": 0.5,
    # serving.py's own note: the batched wall is dispatch amortization
    # with Python thread scheduling riding on top (8 submitter threads +
    # the batcher worker on a CPU CI mesh), so run-to-run spread is
    # scheduler noise, not kernel time
    "serving_batch": 0.5,
    # round-16 quantized rows (quantize.py's own notes): measured from a
    # COLD tuning table like the kernel-tier rows — the timed region
    # includes the explore phase running BOTH arms back to back, and on
    # the CPU CI mesh which arm wins is scheduler-dependent (no int8 MXU
    # path; the win the rows vouch for is the exact-ledger residency
    # columns, which the ci.sh stage-19 gate checks separately)
    "linear_int8": 0.5,
    "moe_ffn_int8": 0.5,
    # single-run batched wall over a thread pool, same contract as
    # serving_batch: Python thread scheduling rides the number
    "serving_knn": 0.5,
    # round-17 quantized-collective rows (wire.py's own notes): the wall
    # rides the FORCED int8 arm, which on the CPU CI mesh is extra work
    # (no ICI to relieve — the quant/dequant pass is pure overhead whose
    # cost depends on host scheduling), so the headline these rows vouch
    # for is the exact wire-ledger byte columns and the measured error
    # bound, both checked by the ci.sh stage-20 gate, not the wall
    "resplit_wire_int8": 0.5,
    "matmul_ring_wire": 0.5,
    # round-18 fleet row (router.py's own note): the wall is a 2-replica
    # fleet ABSORBING a real injected 0.35s replica stall mid-run — the
    # timed region includes the stall, the ejection and the failover
    # re-dispatches, and on the CPU CI mesh both replicas contend for
    # the same host cores under 8 submitter threads, so scheduler noise
    # rides the number; the headline the row vouches for is
    # lost_futures=0 and the measured recovery tail, both asserted
    # inside the workload itself
    "router_failover": 0.5,
    # round-19 sparse-tier rows (sparse.py's own notes): spmv_csr is
    # measured from a COLD tuning table — the timed region includes the
    # explore phase running all three arms, one of which (dense) does a
    # full todense+matmul per call, so the wall rides how quickly the
    # table resolved; the headline the row vouches for is the
    # exact-ledger residency columns, which the ci.sh stage-22 gate
    # checks separately
    "spmv_csr": 0.5,
    # single-run whole-`.fit` wall like the kmeans rows (the estimator's
    # host readbacks ride the number), plus a cold knn top-k compile
    "spectral_sparse": 0.5,
    # single-run batched wall over a thread pool, same contract as
    # serving_batch: Python thread scheduling rides the number
    "serving_knn_graph": 0.5,
    # round-20 streaming rows (stream.py's own notes): single-run walls
    # whose timed region is dominated by host file I/O and the prefetch
    # thread contending with the consumer for the same CPU cores — the
    # headline each row vouches for (peak staging <= budget, centroid
    # parity, zero step compiles) is ASSERTED inside the workload, and
    # the ci.sh stage-23 gate re-checks it; the wall rides the OS page
    # cache and thread scheduling
    "stream_kmeans": 0.5,
    "stream_knn_serving": 0.5,
}

_ROUND_RE = re.compile(r"BENCH_cb_r(\d+)\.json$")


def repo_root() -> str:
    return os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def load_rounds(root=None):
    """Every checked-in round as ``(round_number, path, document)``,
    oldest first."""
    root = root or repo_root()
    out = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_cb_r*.json"))):
        m = _ROUND_RE.search(path)
        if not m:
            continue
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        out.append((int(m.group(1)), path, doc))
    out.sort(key=lambda t: t[0])
    return out


def best_history(rounds, backend, before_round=None):
    """Best (minimum) ``wall_s`` per row name across the rounds matching
    ``backend``; ``before_round`` restricts to strictly earlier rounds
    (the self-check's baseline window)."""
    best = {}
    for rnum, _path, doc in rounds:
        if doc.get("backend") != backend:
            continue
        if before_round is not None and rnum >= before_round:
            continue
        for m in doc.get("measurements", []):
            w = m.get("wall_s")
            name = m.get("name")
            if w is None or name is None:
                continue
            cur = best.get(name)
            if cur is None or w < cur["best_wall_s"]:
                best[name] = {"best_wall_s": w, "round": rnum}
    return best


def compare(measurements, best):
    """Judge ``measurements`` row-by-row against ``best``.  Returns
    ``(rows, regressions)`` — every row gets a delta entry with status
    ``ok`` / ``regression`` / ``no-history``."""
    rows, bad = [], []
    for m in measurements:
        name = m.get("name")
        w = m.get("wall_s")
        if name is None or w is None:
            continue
        h = best.get(name)
        if h is None:
            rows.append({"name": name, "wall_s": w, "status": "no-history"})
            continue
        b = h["best_wall_s"]
        tol = TOLERANCE.get(name, DEFAULT_REL_TOL)
        limit = max(b * (1.0 + tol), b + ABS_FLOOR_S)
        row = {
            "name": name,
            "wall_s": w,
            "best_wall_s": b,
            "best_round": h["round"],
            "ratio": round(w / b, 4) if b > 0 else None,
            "tolerance": tol,
            "limit_s": round(limit, 6),
            "status": "ok" if w <= limit else "regression",
        }
        rows.append(row)
        if row["status"] == "regression":
            bad.append(row)
    return rows, bad


def _print_table(rows, header):
    print(header)
    print(f"  {'row':<36}{'wall_s':>12}{'best':>12}{'ratio':>8}"
          f"{'limit':>12}  status")
    for r in rows:
        if r["status"] == "no-history":
            print(f"  {r['name']:<36}{r['wall_s']:>12.6f}{'-':>12}{'-':>8}"
                  f"{'-':>12}  no-history")
        else:
            print(f"  {r['name']:<36}{r['wall_s']:>12.6f}"
                  f"{r['best_wall_s']:>12.6f}{r['ratio']:>8.3f}"
                  f"{r['limit_s']:>12.6f}  {r['status']}")


def check(doc, root=None):
    """Compare a cb suite document against the checked-in trajectory for
    its backend, attach the delta table as ``doc["regression"]``, print
    it, and return the out-of-tolerance rows."""
    rounds = load_rounds(root)
    backend = doc.get("backend", "cpu")
    best = best_history(rounds, backend)
    rows, bad = compare(doc.get("measurements", []), best)
    doc["regression"] = {
        "backend": backend,
        "baseline_rounds": [r for r, _p, d in rounds
                            if d.get("backend") == backend],
        "rel_tolerance_default": DEFAULT_REL_TOL,
        "abs_floor_s": ABS_FLOOR_S,
        "rows": rows,
        "regressions": [r["name"] for r in bad],
    }
    if not best:
        print(f"check-regression: no checked-in {backend}-backend history — "
              f"{len(rows)} row(s) pass as no-history "
              f"(trajectory rounds are "
              f"{sorted(set(d.get('backend') for _r, _p, d in rounds))})")
    _print_table(rows, f"check-regression vs best {backend} history:")
    if bad:
        print(f"REGRESSION: {len(bad)} row(s) out of tolerance: "
              + ", ".join(r["name"] for r in bad))
    else:
        print("check-regression: all rows within tolerance")
    return bad


def self_check(root=None):
    """Replay the gate on the trajectory itself: the latest checked-in
    round vs the best of the strictly earlier same-backend rounds.
    Returns the out-of-tolerance rows (CI fails on any) — proving on
    every run that the harness actually bites, with no hardware needed."""
    rounds = load_rounds(root)
    if len(rounds) < 2:
        print("self-check: need at least two checked-in rounds")
        return []
    latest_num, latest_path, latest = rounds[-1]
    backend = latest.get("backend", "cpu")
    best = best_history(rounds, backend, before_round=latest_num)
    rows, bad = compare(latest.get("measurements", []), best)
    _print_table(
        rows,
        f"self-check: r{latest_num:02d} ({os.path.basename(latest_path)}) "
        f"vs best of earlier {backend} rounds:",
    )
    if bad:
        print(f"REGRESSION in checked-in trajectory: "
              + ", ".join(r["name"] for r in bad))
    else:
        print(f"self-check OK: {len(rows)} rows within tolerance")
    return bad


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--self-check", action="store_true",
                    help="gate the latest checked-in round against the "
                         "best of the earlier ones")
    ap.add_argument("--root", default=None,
                    help="repo root holding BENCH_cb_r*.json")
    args = ap.parse_args()
    if args.self_check:
        sys.exit(1 if self_check(args.root) else 0)
    ap.error("nothing to do (pass --self-check, or use main.py "
             "--check-regression for a live run)")
