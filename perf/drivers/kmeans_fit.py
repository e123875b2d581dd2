"""Each call is one ``ht.cluster.KMeans(...).fit(X)`` on the resident X."""

import jax.numpy as jnp
import numpy as np

from perf.drivers import Arr, _kmeans
from perf.reference import lloyd


def setup(ctx):
    x = ctx.ht.array(ctx.data["x"], split=ctx.config["split"])
    return {"ht": ctx.ht, "x": x, "fit": ctx.config["fit"], "seed": ctx.seed}


def call(state, item):
    x, cfg = state["x"], state["fit"]
    start = _kmeans.init_rows(state["seed"], item["index"], x.shape[0], cfg["n_clusters"])
    est = _kmeans.fit(state["ht"], x, cfg, start)
    return {
        "centers": est.cluster_centers_, "labels": est.labels_,
        "inertia": est.inertia_, "n_iter": est.n_iter_, "start": start,
    }


def keep(state, item, out):
    return out


def release(state):
    state.pop("ht", None)


def matched(c, c_ref):
    """The reference's centres in the program's order: the one-to-one pairing
    of least total squared distance.  A cluster's number is no part of the
    answer.  From the same start both sides keep the same numbers, except
    where two centres leave a near-tie between two blobs in opposite
    directions: then the same clustering comes out with two numbers swapped
    (1 fit in some 150 at the cell's size; PERF.md, "How correct is decided").
    Returns the permuted reference centres and how many clusters moved."""
    from scipy.optimize import linear_sum_assignment

    c, c_ref = np.asarray(c, np.float64), np.asarray(c_ref, np.float64)
    cost = ((c[:, None, :] - c_ref[None, :, :]) ** 2).sum(-1)
    _, match = linear_sum_assignment(cost)
    return jnp.asarray(c_ref[match], jnp.float32), int((match != np.arange(len(match))).sum())


def _apart(c, c_ref):
    """Relative distance of ``c`` from the reference's centres, paired."""
    paired, moved = matched(c, c_ref)
    return float(jnp.linalg.norm(c - paired) / jnp.linalg.norm(c_ref)), moved


def judge_fit(x, kept, fit_cfg, check_cfg):
    """Numbers of one kept fit against the reference from the same start.

    ``centers_err`` is None where the start itself is ill-conditioned: the
    fit reads over the limit AND so does the reference computed with the
    operands the configuration states (``check.stated_operands``), i.e. the
    float32 reference departs that far from itself at the stated precision
    (a slow escape from a saddle multiplies a step's rounding by 1.25 an
    iteration; 1 fit in some 500, PERF.md).  Such a fit's centres are left out
    by this rule on the reference; its labels and iterations are judged."""
    init = _kmeans.rows_of(x, jnp.asarray(kept["start"]))
    c_ref, inertia_ref = lloyd.fit(x, init, fit_cfg["max_iter"])
    c = jnp.asarray(kept["centers"].larray, jnp.float32)
    by_number = float(jnp.linalg.norm(c - c_ref) / jnp.linalg.norm(c_ref))
    err, moved = _apart(c, c_ref)
    left_out = False
    if not err <= check_cfg["limits"]["centers_err"]:
        stated, _ = lloyd.fit(x, init, fit_cfg["max_iter"], operands=check_cfg["stated_operands"])
        left_out = _apart(stated, c_ref)[0] > check_cfg["limits"]["centers_err"]
    # the labels are the assignment to the fit's OWN final centres (the
    # configuration's guarantee), judged by the reference's float32 distances
    gap = lloyd.label_gap(x, kept["labels"].larray.reshape(-1), c)
    # inertia is not compared: it is a sum over all rows in which the errors
    # of either sign cancel, so the control reads no higher than the program
    # (PERF.md, "How correct is decided"); it is printed for the record
    return {
        "centers_err": None if left_out else err,
        "label_gap": float(gap["widest"]),
        "n_iter_off": float(abs(int(kept["n_iter"]) - fit_cfg["max_iter"])),
    }, {
        "inertia_err": abs(float(kept["inertia"]) - float(inertia_ref)) / float(inertia_ref),
        "label_gap_mean": float(gap["mean"]), "label_differ_share": float(gap["differ"]),
        "clusters_renumbered": float(moved), "centers_err_by_number": by_number,
        "starts_left_out": float(left_out), "centers_err_left_out": err if left_out else 0.0,
    }


def check(state, kept, ctx):
    x = state["x"].larray
    numbers, info = {}, {}
    for one in kept:
        got, extra = judge_fit(x, one, state["fit"], ctx.workload["check"])
        for name, val in got.items():
            if val is not None:
                numbers[name] = max(numbers.get(name, 0.0), val) if np.isfinite(val) else val
        for name, val in extra.items():
            info[name] = max(info.get(name, 0.0), val)
    # a window whose every sampled start was left out has compared no centres
    # (a finite number: the result's line has to stay plain JSON)
    numbers.setdefault("centers_err", 1e30)
    return numbers, info


def control(state, item, ctx):
    """The reference in the program's place, with the GEMM operands rounded to fp8."""
    x, cfg = state["x"].larray, state["fit"]
    start = _kmeans.init_rows(state["seed"], item["index"], x.shape[0], cfg["n_clusters"])
    low = dict(operands=ctx.workload["check"].get("control_operands", "float8_e4m3"))
    init = _kmeans.rows_of(x, jnp.asarray(start))
    centres, inertia = lloyd.fit(x, init, cfg["max_iter"], **low)
    labels = lloyd.assign(x, centres, **low)
    return {
        "centers": Arr(centres), "labels": Arr(labels.reshape(-1, 1)),
        "inertia": float(inertia), "n_iter": cfg["max_iter"], "start": start,
    }
