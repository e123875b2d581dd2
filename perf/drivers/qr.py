"""Each call is ``q, r = ht.linalg.qr(A)`` on the resident A, with the
keyword arguments of the workload's ``call`` block (none: the defaults)."""

from perf.drivers import Arr
from perf.reference import qr as ref_qr


def setup(ctx):
    a = ctx.ht.array(ctx.data["a"], split=ctx.config["split"])
    return {"ht": ctx.ht, "a": a, "kwargs": dict(ctx.workload.get("call", {}))}


def call(state, item):
    q, r = state["ht"].linalg.qr(state["a"], **state["kwargs"])
    return {"q": q, "r": r}


def keep(state, item, out):
    return out


def release(state):
    state.pop("ht", None)


def judge(a, q, r):
    return {k: float(v) for k, v in ref_qr.properties(a, q, r).items()}


def check(state, kept, ctx):
    numbers = {}
    a = state["a"]
    for one in kept:
        q, r = one["q"], one["r"]
        if tuple(q.shape) != tuple(a.shape) or tuple(r.shape) != (a.shape[1],) * 2:
            return {"bad_shape": 1.0}, {}
        for name, val in judge(a.larray, q.larray, r.larray).items():
            numbers[name] = max(numbers.get(name, 0.0), val)
    numbers["bad_shape"] = 0.0
    return numbers, {}


def control(state, item, ctx):
    """``float32`` at ``highest`` is what the configuration states.  Where
    the workload names the program's own lower-precision path (``control``:
    keyword arguments of ``ht.linalg.qr``), that path is the control;
    otherwise the plain CholeskyQR2 at ``high`` (three passes) stands in."""
    own = ctx.workload["check"].get("control")
    if own is not None:
        q, r = state["ht"].linalg.qr(state["a"], **own)
        return {"q": q, "r": r}
    q, r = ref_qr.cholesky_qr2(state["a"].larray, precision="high")
    return {"q": Arr(q), "r": Arr(r)}
