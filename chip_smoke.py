#!/usr/bin/env python3
"""chip_smoke.py — run heat_tpu's main path once on the chip.

The quickest proof that the system still starts on a TPU.  One process, no
children.  It drives the array layer, the estimators, the trainer, the
server and every Pallas kernel through the entry points a user calls
(``import heat_tpu as ht``), at the widths of ``benchmarks/cb/config.py``'s
TPU sizes (rows and batches are per chip: multiplied by the device count),
for a few iterations each with seeded random data, and checks each result
against a plain reference on a slice or a small input.  Then it reads the
program's own counters and fails on anything that ran degraded: a fused
program that fell back to eager, a transport OOM retry, a telemetry
``fallback`` event, an autotune arm recorded at ``inf``, a kernel site whose
mode was not ``tpu``, a serving step compiled after warm-up.

Contract:
  * no TPU -> exit 2 with a message naming what JAX found, no result line;
  * any failed stage or counter -> exit 1, last stdout line {"ok": false, ...};
  * all green -> exit 0, last stdout line
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.

Per-stage seconds (first call = compile + run, steady call) are set-up
information, printed under no metric name.

``python chip_smoke.py --rehearse-cpu [N]`` is the one argument: a
rehearsal of the same stages at toy sizes on N (default 4) virtual CPU
devices with the kernels in interpret mode and 32-bit types as on the chip.
It is for debugging the script before spending chip time; every line it
prints says REHEARSAL and its last line carries "rehearsal": true.  It is
never what happens when no chip is found.
"""

import json
import os
import sys
import threading
import time
import traceback

REHEARSAL = len(sys.argv) > 1 and sys.argv[1] == "--rehearse-cpu"
if len(sys.argv) > 1 and not REHEARSAL:
    sys.exit(f"chip_smoke.py: unknown argument {sys.argv[1]!r} "
             "(the only one is --rehearse-cpu [N])")
if REHEARSAL:
    _n = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_n}"
    )
    os.environ["HEAT_TPU_PALLAS"] = "interpret"
    os.environ["HEAT_TPU_X64"] = "0"  # 32-bit types, as on the chip

import jax  # noqa: E402

TAG = "REHEARSAL " if REHEARSAL else ""


def say(*parts):
    print(TAG + " ".join(str(p) for p in parts), flush=True)


_dev0 = jax.devices()[0]
DEVICE = {
    "platform": _dev0.platform,
    "kind": _dev0.device_kind,
    "count": len(jax.devices()),
}
if not REHEARSAL and DEVICE["platform"] != "tpu":
    sys.stderr.write(
        "chip_smoke.py needs a TPU; JAX found platform "
        f"{DEVICE['platform']!r} (device_kind {DEVICE['kind']!r}, "
        f"{DEVICE['count']} device(s)).  Nothing was run.\n"
    )
    sys.exit(2)

try:
    import heat_tpu as ht
except ImportError as exc:
    sys.stderr.write(
        f"chip_smoke.py drives the heat_tpu package beside it: {exc}\n"
    )
    sys.exit(2)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from heat_tpu import native, serving  # noqa: E402
from heat_tpu.core import autotune, fusion, telemetry, wire  # noqa: E402
from heat_tpu.core.dndarray import DNDarray  # noqa: E402
from heat_tpu.ops import (_pallas_common, attention, lasso_sweep, latent_attention,  # noqa: E402
                          latent_stream, lloyd_pass, qr_panel)
from heat_tpu.ops import cdist as cdist_kernel  # noqa: E402
from heat_tpu.parallel import overlap, transport  # noqa: E402
from heat_tpu.utils import compile_cache  # noqa: E402

NDEV = DEVICE["count"]
HI = jax.lax.Precision.HIGHEST
KERNEL_MODE = "interpret" if REHEARSAL else "tpu"

# Widths are the cb TPU sizes (benchmarks/cb/config.py); rows and batches
# are per chip.  The rehearsal column only has to reach every code path.
FULL = dict(
    matmul_n=8192, odd=(1031, 517), reshape_in=(999_999, 20),
    reshape_out=(1_999_998, 10), qr=(1_000_000, 128),
    kmeans=(20_000_000, 64, 8), kmeans_iters=5, lasso=(8192, 512),
    cdist=(65_536, 4096, 64), spmv_n=131_072, spmv_row_nnz=262,
    resnet=(256, 224), resnet_classes=1000, serve_f=64, serve_reqs=48,
    attn=(16, 4096, 128), moe=(16_384, 1024, 4096), qr_panel=(262_144, 256),
    fence_k=(8, 108), wire_resplit=(16_384, 4096), ring_mm=(4096, 8192, 4096),
    sp_attn=(2, 8, 4096, 128), pipe=(512, 1024), sparse_cut=(16, 33_024, 2048),
)
TOY = dict(
    matmul_n=256, odd=(131, 67), reshape_in=(999, 20), reshape_out=(1998, 10),
    qr=(4096, 128), kmeans=(4096, 16, 4), kmeans_iters=3, lasso=(1024, 64),
    cdist=(512, 256, 16), spmv_n=512, spmv_row_nnz=8, resnet=(8, 32),
    resnet_classes=10, serve_f=16, serve_reqs=24, attn=(2, 256, 32),
    moe=(256, 64, 128), qr_panel=(2048, 128), fence_k=(2, 6),
    wire_resplit=(512, 256), ring_mm=(256, 512, 256), sp_attn=(1, 4, 256, 32),
    pipe=(32, 64), sparse_cut=(3, 384, 128),
)
SZ = TOY if REHEARSAL else FULL


class Failed(Exception):
    """A stage's result was wrong."""


def check(cond, msg):
    if not cond:
        raise Failed(msg)


def ready(x):
    """Block until every device array inside ``x`` is ready; returns x."""
    leaves = jax.tree.leaves(x, is_leaf=lambda v: isinstance(v, DNDarray))
    jax.block_until_ready(
        [v.larray if isinstance(v, DNDarray) else v for v in leaves]
    )
    return x


def clocked(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = ready(fn(*args, **kwargs))
    return out, time.perf_counter() - t0


def first_and_steady(fn, steady_calls=1):
    """(result of the last call, first-call seconds, best steady seconds)."""
    out, first = clocked(fn)
    steady = []
    for _ in range(steady_calls):
        out, t = clocked(fn)
        steady.append(t)
    return out, first, min(steady)


def close(got, want, tol, what):
    """max|got-want| <= tol * max(1, max|want|); both finite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    check(got.shape == want.shape, f"{what}: shape {got.shape} != {want.shape}")
    check(np.isfinite(got).all(), f"{what}: non-finite values")
    err = float(np.abs(got - want).max())
    bound = tol * max(1.0, float(np.abs(want).max()))
    check(err <= bound, f"{what}: max error {err:.3e} > {bound:.3e}")
    return err


def placed_everywhere(x: DNDarray, what):
    """A split DNDarray's shards sit on every device of the mesh."""
    if x.split is None:
        return
    devs = {s.device for s in x.larray.addressable_shards}
    check(
        len(devs) == NDEV,
        f"{what}: shards on {len(devs)} device(s), mesh has {NDEV}",
    )


def bytes_in_use():
    out = []
    for d in jax.devices():
        st = d.memory_stats()
        out.append(None if not st else int(st.get("bytes_in_use", 0)))
    return out


RESULTS = []
_event_mark = [0]


def new_fallback_events():
    """``fallback`` events recorded since the last call (read after every
    stage, so the flight recorder's ring cannot have rotated them out)."""
    evs = telemetry.events(since=_event_mark[0])
    if evs:
        _event_mark[0] = evs[-1]["seq"]
    return [e for e in evs if e["kind"] == "fallback"]


def stage(name, fn):
    say(f"--- stage {name}")
    rec = {"stage": name, "ok": False}
    t0 = time.perf_counter()
    try:
        info = fn() or {}
        rec.update(info)
        rec["ok"] = True
    except Exception as exc:  # a stage failure is recorded; later stages still run
        rec["error"] = f"{type(exc).__name__}: {exc}"[:2000]
        traceback.print_exc()
    rec["total_s"] = round(time.perf_counter() - t0, 3)
    fb = new_fallback_events()
    if fb:
        rec["fallback_events"] = fb[:8]
    RESULTS.append(rec)
    say("stage", json.dumps(rec, default=str))


def tuned_kernel_rows():
    """Tuning-table entries of the classic-vs-kernel sites."""
    return [
        r for r in autotune.report()["rows"]
        if tuple(r.get("arms", ())) == _pallas_common.KERNEL_ARMS
    ]


# ------------------------------------------------------------------ stages


def st_matmul():
    n = SZ["matmul_n"]
    a = ht.random.randn(n * NDEV, n, dtype=ht.bfloat16, split=0)
    b = ht.random.randn(n, n, dtype=ht.bfloat16, split=None)
    placed_everywhere(a, "matmul lhs")
    c, first, steady = first_and_steady(lambda: ht.matmul(a, b))
    placed_everywhere(c, "matmul out")
    check(c.shape == (n * NDEV, n), f"matmul shape {c.shape}")
    rows = min(256, n)
    ref = jnp.matmul(
        a.larray[:rows].astype(jnp.float32), b.larray.astype(jnp.float32),
        precision=HI,
    )
    err = close(c.larray[:rows].astype(jnp.float32), ref, 1e-2, "matmul vs f32 HIGHEST")
    return {"first_s": round(first, 3), "steady_s": round(steady, 4), "max_err": err}


def st_resplit():
    shape = SZ["odd"]
    host = np.random.default_rng(1).standard_normal(shape).astype(np.float32)
    x = ht.array(host, split=0)
    t0 = time.perf_counter()
    for split in (1, None, 0):
        x.resplit_(split)
        check(x.split == split, f"resplit_ -> {split} left split {x.split}")
        placed_everywhere(x, f"resplit_ -> {split}")
        check(np.array_equal(x.numpy(), host), f"resplit_ -> {split} changed values")
    return {"first_s": round(time.perf_counter() - t0, 3)}


def st_reshape():
    """Split-crossing reshape to a narrow minor (10 of 128 lanes); the source
    shards carry pad rows when rows % mesh != 0."""
    gin, gout = SZ["reshape_in"], SZ["reshape_out"]
    host = np.random.default_rng(2).standard_normal(gin).astype(np.float32)
    x = ht.array(host, split=0)
    out, first, steady = first_and_steady(lambda: ht.reshape(x, gout))
    check(out.shape == tuple(gout), f"reshape shape {out.shape}")
    placed_everywhere(out, "reshape out")
    check(np.array_equal(out.numpy(), host.reshape(gout)), "reshape changed values")
    return {"first_s": round(first, 3), "steady_s": round(steady, 4)}


def st_qr():
    m, n = SZ["qr"]
    a = ht.random.randn(m * NDEV, n, split=0)
    calls = autotune.explore_k() + 1
    times = []
    for _ in range(calls):
        (q, r), t = clocked(lambda: tuple(ht.linalg.qr(a)))
        times.append(t)
    placed_everywhere(q, "qr Q")
    check(q.shape == (m * NDEV, n) and r.shape == (n, n), "qr shapes")
    qtq = jnp.matmul(q.larray.T, q.larray, precision=HI)
    close(qtq, np.eye(n), 1e-4, "QtQ vs I")
    rows = min(4096, m)
    close(
        jnp.matmul(q.larray[:rows], r.larray, precision=HI), a.larray[:rows],
        1e-4, "QR vs A on a slice",
    )
    info = {"first_s": round(times[0], 3), "steady_s": round(min(times[1:]), 4)}
    if NDEV > 1:
        # split=0 over several chips took the TSQR tree above; the fused
        # panel kernel is reached by a replicated operand (run per device)
        m, n = SZ["qr_panel"]
        a = ht.random.randn(m, n, split=None)
        for _ in range(calls):
            (q, r), t = clocked(lambda: tuple(ht.linalg.qr(a)))
        close(jnp.matmul(q.larray.T, q.larray, precision=HI), np.eye(n), 1e-4,
              "replicated QtQ vs I")
        info["replicated_steady_s"] = round(t, 4)
    mode = qr_panel.panel_mode(m, n, jnp.float32, False, a.split, NDEV)
    check(mode == KERNEL_MODE, f"qr_panel mode {mode!r}, wanted {KERNEL_MODE!r}")
    rows_t = [r_ for r_ in tuned_kernel_rows() if r_["desc"].startswith("qr ")]
    check(rows_t, "qr: no classic/kernel tuning entry — kernel arm not explored")
    info["winner"] = rows_t[0]["winner"]
    return info


def _numpy_lloyd(x, centers, iters):
    for _ in range(iters):
        d = ((x[:, None, :] - centers[None, :, :]) ** 2).sum(-1)
        lab = d.argmin(1)
        centers = np.stack([
            x[lab == j].mean(0) if (lab == j).any() else centers[j]
            for j in range(centers.shape[0])
        ])
    return centers


def st_kmeans():
    n, f, k = SZ["kmeans"]
    base = bytes_in_use()
    data = ht.random.randn(n * NDEV, f, split=0)
    ready(data)
    placed_everywhere(data, "kmeans data")
    held = bytes_in_use()
    if all(b is not None for b in held):
        share = n * f * 4
        for i, (b0, b1) in enumerate(zip(base, held)):
            check(
                b1 - b0 >= 0.9 * share,
                f"bytes_in_use on device {i} rose {b1 - b0} B for a "
                f"{share} B shard — data is not on every chip",
            )
    else:
        check(REHEARSAL, "memory_stats() returned nothing on a TPU")

    def fit():
        est = ht.cluster.KMeans(
            n_clusters=k, init="random", max_iter=SZ["kmeans_iters"],
            tol=-1.0, random_state=7,
        )
        est.fit(data)
        return est.cluster_centers_

    centers, first, steady = first_and_steady(fit)
    bodies = {e.get("lloyd") for e in telemetry.events("span_end") if e["name"] == "kmeans.fit"}
    check(bodies == {"fused"}, f"Lloyd bodies that ran: {bodies}, wanted the fused pass alone")
    assigned = {e.get("assign") for e in telemetry.events("span_end") if e["name"] == "kmeans.labels"}
    check(assigned == {"fused"}, f"what made the labels: {assigned}, wanted the fused pass alone")
    check(centers.shape == (k, f), f"centers shape {centers.shape}")
    check(bool(np.isfinite(centers.numpy()).all()), "non-finite centers")
    del data

    # agreement with a plain reference on a small input.  Blobs around k
    # random sign vectors, one init point per blob: the squared distance
    # between blobs (~2f) dwarfs the error of the default-precision (bf16
    # pass) distance matmul (~f/256), so the assignments must be the
    # reference's; the centre update is a one-hot GEMM at the same default
    # precision, which bounds the agreement at ~2^-9 of the values (~1).
    rng = np.random.default_rng(3)
    blob = np.arange(2048) % k
    signs = rng.choice([-1.0, 1.0], size=(k, f))
    small = (signs[blob] + 0.1 * rng.standard_normal((2048, f))).astype(np.float32)
    init = small[:k].copy()
    est = ht.cluster.KMeans(n_clusters=k, init=ht.array(init), max_iter=3, tol=-1.0)
    est.fit(ht.array(small, split=0))
    err = close(est.cluster_centers_.numpy(), _numpy_lloyd(small, init, 3), 2e-3,
                "kmeans vs numpy Lloyd")
    labels = est.labels_.numpy()
    check(labels.shape == (2048, 1) and bool((labels.ravel() == blob).all()),
          f"kmeans labels_: {int((labels.ravel() != blob).sum())} of 2048 rows off their blob")
    return {"first_s": round(first, 3), "steady_s": round(steady, 4),
            "bytes_in_use_rise": [None if b1 is None else b1 - b0
                                  for b0, b1 in zip(base, held)],
            "small_input_err": err}


def _numpy_cd(xa, y, lam, sweeps):
    m, n = xa.shape
    th = np.zeros(n)
    r = y - xa @ th
    for _ in range(sweeps):
        for j in range(n):
            xj = xa[:, j]
            rho = xj @ (r + th[j] * xj) / m
            new = rho if j == 0 else np.sign(rho) * max(abs(rho) - lam, 0.0)
            r = r + (th[j] - new) * xj
            th[j] = new
    return th


def st_lasso():
    m, n = SZ["lasso"]
    rng = np.random.default_rng(4)
    X = rng.standard_normal((m, n)).astype(np.float32)
    X /= np.sqrt((X * X).mean(axis=0)) + 1e-12
    beta = np.zeros((n, 1), np.float32)
    beta[:: max(n // 16, 1)] = 2.0
    y = X @ beta + 0.01 * rng.standard_normal((m, 1)).astype(np.float32)
    xa, ya = ht.array(X), ht.array(y)
    sweeps = 3
    mode = lasso_sweep.sweep_mode(m, n + 1, jnp.float32, None, NDEV)
    check(mode == KERNEL_MODE, f"lasso_sweep mode {mode!r}, wanted {KERNEL_MODE!r}")

    def fit():
        est = ht.regression.Lasso(lam=0.01, max_iter=sweeps, tol=-1.0)
        est.fit(xa, ya)
        return est.theta

    want = _numpy_cd(
        np.concatenate([np.ones((m, 1)), X.astype(np.float64)], 1),
        y[:, 0].astype(np.float64), 0.01, sweeps,
    )
    calls = autotune.explore_k() + 1
    times = []
    for i in range(calls):
        theta, t = clocked(fit)
        times.append(t)
        close(theta.numpy()[:, 0], want, 1e-3, f"lasso theta vs numpy CD (call {i})")
    rows_t = [r_ for r_ in tuned_kernel_rows() if r_["desc"].startswith("lasso ")]
    check(rows_t, "lasso: no classic/kernel tuning entry — kernel arm not explored")
    return {"first_s": round(times[0], 3), "steady_s": round(min(times[1:]), 4),
            "winner": rows_t[0]["winner"]}


def st_cdist():
    nx, ny, f = SZ["cdist"]
    x = ht.random.randn(nx * NDEV, f, split=0)
    y = ht.random.randn(ny, f, split=None)
    d, first, steady = first_and_steady(lambda: ht.spatial.cdist(x, y))
    placed_everywhere(d, "cdist out")
    check(d.shape == (nx * NDEV, ny), f"cdist shape {d.shape}")
    xs = np.asarray(x.larray[:128], np.float64)
    ys = np.asarray(y.larray, np.float64)
    ref = np.sqrt(((xs[:, None, :] - ys[None, :, :]) ** 2).sum(-1))
    err = close(d.larray[:128], ref, 2e-3, "cdist vs numpy on a slice")
    return {"first_s": round(first, 3), "steady_s": round(steady, 4), "max_err": err}


def st_spmv():
    import scipy.sparse

    n, k = SZ["spmv_n"] * NDEV, SZ["spmv_row_nnz"]
    rng = np.random.default_rng(5)
    # k distinct columns per row: a random start and an odd stride
    cols = (rng.integers(0, n, (n, 1)) + np.arange(k)[None, :] * 12_289) % n
    cols.sort(axis=1)
    sp = scipy.sparse.csr_matrix(
        (rng.standard_normal(n * k).astype(np.float32), cols.reshape(-1).astype(np.int32),
         np.arange(n + 1, dtype=np.int64) * k),
        shape=(n, n),
    )
    xh = rng.standard_normal(n).astype(np.float32)
    A = ht.sparse.sparse_csr_matrix(sp, split=0)
    x = ht.array(xh)
    y, first, steady = first_and_steady(lambda: A @ x, steady_calls=autotune.explore_k())
    placed_everywhere(y, "spmv out")
    err = close(y.numpy(), sp @ xh, 1e-4, "DCSR @ x vs scipy")
    return {"first_s": round(first, 3), "steady_s": round(steady, 4),
            "nnz": int(A.nnz), "max_err": err}


def st_resnet():
    import optax

    b, img = SZ["resnet"]
    b *= NDEV
    rng = np.random.default_rng(6)
    Xh = rng.standard_normal((b, img, img, 3), dtype=np.float32).astype(jnp.bfloat16)
    yh = rng.integers(0, SZ["resnet_classes"], b)
    model = ht.nn.DataParallel(
        ht.models.ResNet50(num_classes=SZ["resnet_classes"], dtype=jnp.bfloat16),
        optimizer=ht.optim.DataParallelOptimizer(optax.sgd(0.1)),
    )
    model.init(0, Xh[: min(b, 8)])
    X = ht.array(Xh, split=0)
    y = ht.array(yh, split=0)
    placed_everywhere(X, "resnet batch")
    losses, times = [], []
    for _ in range(3):
        loss, t = clocked(lambda: model.train_step(X, y))
        losses.append(float(loss))
        times.append(t)
    check(all(np.isfinite(losses)), f"non-finite loss {losses}")
    check(len(set(losses)) == 3, f"loss did not change over 3 steps: {losses}")
    return {"first_s": round(times[0], 3), "steady_s": round(min(times[1:]), 4),
            "losses": [round(v, 4) for v in losses]}


def st_serving():
    f = SZ["serve_f"]
    rng = np.random.default_rng(7)
    km = ht.cluster.KMeans(n_clusters=8, init="kmeans++", max_iter=5, random_state=0)
    km.fit(ht.array(rng.standard_normal((512, f)).astype(np.float32), split=0))
    requests = [
        rng.standard_normal((int(r), f)).astype(np.float32)
        for r in rng.integers(1, 5, size=SZ["serve_reqs"])
    ]
    want = [km.predict(ht.array(r, split=0)).numpy().reshape(-1) for r in requests]
    telemetry.reset_group("serving")
    eng = serving.ServingEngine()
    try:
        t0 = time.perf_counter()
        eng.register("km", km, feature_dim=f, min_bucket=8, max_batch=32,
                     max_delay_s=0.002, warm=True)
        warm_s = time.perf_counter() - t0
        compiles_warm = eng.stats()["step_compiles"]
        futures = [None] * len(requests)

        def submitter(lo):
            for i in range(lo, len(requests), 6):
                futures[i] = eng.submit("km", requests[i])

        t0 = time.perf_counter()
        threads = [threading.Thread(target=submitter, args=(lo,)) for lo in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            check(not t.is_alive(), "a submitter thread did not finish")
        got = [fut.result(120) for fut in futures]
        served_s = time.perf_counter() - t0
        stats = eng.stats()
    finally:
        eng.close()
    for g, w in zip(got, want):
        check(np.array_equal(np.asarray(g).reshape(-1), w), "served labels != direct predict")
    late = stats["step_compiles"] - compiles_warm
    check(late == 0, f"{late} serving step compile(s) after warm-up")
    return {"first_s": round(warm_s, 3), "steady_s": round(served_s, 4),
            "requests": len(requests), "batches": stats["batches"],
            "step_compiles_after_warm": late}


def st_flash_attention():
    bh, s, d = SZ["attn"]
    rng = np.random.default_rng(8)
    q, k, v = (jnp.asarray(rng.standard_normal((bh, s, d)), jnp.bfloat16) for _ in range(3))
    info = {}
    for causal in (True, False):
        fn = jax.jit(lambda q_, k_, v_, c=causal: attention.flash_attention(q_, k_, v_, causal=c))
        out, first, steady = first_and_steady(lambda: fn(q, k, v))
        ref = attention._attention_ref(q[:2], k[:2], v[:2], causal, 1.0 / d ** 0.5)
        err = close(out[:2].astype(jnp.float32), ref.astype(jnp.float32), 2e-2,
                    f"flash attention causal={causal} vs reference")
        tag = "causal" if causal else "full"
        info.update({f"{tag}_first_s": round(first, 3),
                     f"{tag}_steady_s": round(steady, 4), f"{tag}_err": err})
    return info


def _moe_reference(x, gate_w, w_in, w_out, k):
    """Dropless dense top-k MoE in f32 (valid while nothing is dropped)."""
    x = x.astype(jnp.float32)
    probs = jax.nn.softmax(x @ gate_w.astype(jnp.float32), axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    hidden = jax.nn.gelu(jnp.einsum("td,edh->eth", x, w_in.astype(jnp.float32), precision=HI))
    outs = jnp.einsum("eth,ehd->etd", hidden, w_out.astype(jnp.float32), precision=HI)
    weight = (jax.nn.one_hot(top_idx, gate_w.shape[1]) * top_w[..., None]).sum(1)  # (t, E)
    return jnp.einsum("te,etd->td", weight, outs)


def _moe_operands(t, d, h, e, dtype, seed):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.standard_normal((t, d)), dtype),
        jnp.asarray(rng.standard_normal((d, e)) / np.sqrt(d), dtype),
        jnp.asarray(rng.standard_normal((e, d, h)) / np.sqrt(d), dtype),
        jnp.asarray(rng.standard_normal((e, h, d)) / np.sqrt(h), dtype),
    )


def st_moe():
    from heat_tpu.parallel.expert import moe_ffn

    t, d, h = SZ["moe"]
    x, gate, w_in, w_out = _moe_operands(t, d, h, 8, jnp.bfloat16, 9)
    fn = jax.jit(lambda *a: moe_ffn(*a, k=2))
    (y, aux), first, steady = first_and_steady(lambda: fn(x, gate, w_in, w_out))
    check(y.shape == x.shape, f"moe shape {y.shape}")
    check(bool(jnp.isfinite(y.astype(jnp.float32)).all()), "non-finite MoE output")
    # small input, f32, capacity wide enough that nothing drops
    xs, gs, wi, wo = _moe_operands(256, 64, 128, 8, jnp.float32, 10)
    ys, auxs = moe_ffn(xs, gs, wi, wo, k=2, capacity_factor=8.0)
    check(float(auxs["fraction_dropped"]) == 0.0, "reference run dropped tokens")
    err = close(ys, _moe_reference(xs, gs, wi, wo, 2), 1e-2, "moe_ffn vs dense reference")
    return {"first_s": round(first, 3), "steady_s": round(steady, 4),
            "fraction_dropped": float(aux["fraction_dropped"]), "small_input_err": err}


def st_kernels():
    """Each Pallas kernel called by name, compiled by Mosaic (mode ``tpu``),
    against its classic arm.  The operands are single-device programs."""
    interp = REHEARSAL
    rng = np.random.default_rng(11)
    info = {}

    nx, ny, f = SZ["cdist"]
    x = jnp.asarray(rng.standard_normal((min(nx, 8192), f)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((ny, f)), jnp.float32)
    got, first, steady = first_and_steady(
        lambda: cdist_kernel._cdist_pallas(x, y, interpret=interp))
    want = jnp.sqrt(jnp.maximum(
        (x * x).sum(1)[:, None] + (y * y).sum(1)[None, :]
        - 2.0 * jnp.matmul(x, y.T, precision=HI), 0.0))
    info["cdist"] = [round(first, 3), round(steady, 4),
                     close(got, want, 2e-3, "cdist kernel vs expansion")]

    # the fused Lloyd pass over rows that end inside a tile
    n, f, k = SZ["kmeans"]
    n = min(n, 41_037)  # two and a half tiles of 16,384 rows at f = 64
    rows = jnp.asarray(rng.standard_normal((n, f)), jnp.float32)
    cen = rows[:k] + 0.5
    fused = jax.jit(lambda v, c: lloyd_pass._pass_pallas(v.T, c, n, interpret=interp, labels=True))
    got, first, steady = first_and_steady(lambda: fused(rows, cen))
    want = lloyd_pass._pass_jnp(rows.T, cen, n, labels=True)
    # a row or two may sit on a tie that the two products' rounding breaks
    check(float(jnp.abs(got[1] - want[1]).max()) <= 2, f"lloyd_pass counts {got[1]} vs {want[1]}")
    check(int((got[3] != want[3]).sum()) <= 2, "lloyd_pass labels vs jax.numpy")
    info["lloyd_pass"] = [round(first, 3), round(steady, 4), max(
        close(got[0] / n, want[0] / n, 1e-4, "lloyd_pass sums vs jax.numpy"),
        close(got[2] / n, want[2] / n, 1e-4, "lloyd_pass inertia vs jax.numpy"))]

    m, n = SZ["qr_panel"]
    mode = qr_panel.panel_mode(m, n, jnp.float32, False, None, 1)
    check(mode == KERNEL_MODE, f"qr_panel mode {mode!r}")
    a = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    fused = jax.jit(lambda v: qr_panel.fused_gram_chol(v, interpret=interp))
    (r, rinv), first, steady = first_and_steady(lambda: fused(a))
    l = jnp.linalg.cholesky(jax.lax.dot_general(a, a, (((0,), (0,)), ((), ())), precision=HI))
    err = close(r, l.T, 1e-4, "qr_panel R vs cholesky")
    close(jnp.matmul(rinv, r, precision=HI), np.eye(n), 1e-3, "qr_panel Rinv @ R vs I")
    info["qr_panel"] = [round(first, 3), round(steady, 4), err]

    from heat_tpu.regression.lasso import _cd_sweep

    m, n = SZ["lasso"]
    mode = lasso_sweep.sweep_mode(m, n + 1, jnp.float32, None, 1)
    check(mode == KERNEL_MODE, f"lasso_sweep mode {mode!r}")
    X = jnp.asarray(rng.standard_normal((m, n + 1)), jnp.float32).at[:, 0].set(1.0)
    yv = jnp.asarray(rng.standard_normal(m), jnp.float32)
    th = jnp.asarray(rng.standard_normal(n + 1) * 0.1, jnp.float32)
    fused = jax.jit(lambda *a_: lasso_sweep.sweep(*a_, 0.05, interpret=interp))
    got, first, steady = first_and_steady(lambda: fused(X, yv, th))
    err = close(got, _cd_sweep(X, yv, th, 0.05), 1e-4, "lasso_sweep kernel vs classic sweep")
    info["lasso_sweep"] = [round(first, 3), round(steady, 4), err]

    # the sparse selection at the benchmark cell's shape: relu'd scores (a third of a row
    # is one value, so ties straddle the cut in some rows), a short row, a row of zeros
    batch, capacity, k = SZ["sparse_cut"]
    form = latent_attention.selection_form(batch, capacity, k)
    check(form == "cut_kernel", f"sparse_cut form {form!r}")
    score = np.maximum(rng.standard_normal((batch, capacity)) + 0.4, 0).astype(np.float32)
    score[0, : capacity // 2] *= 0
    score[1, k // 2:] = -np.inf
    score[:, capacity - 244:] = -np.inf
    score = jnp.asarray(score)
    fused = jax.jit(lambda v: latent_attention.largest_slots(v, k))
    got, first, steady = first_and_steady(lambda: fused(score))
    top, want = jax.lax.top_k(score, k)
    want = np.sort(np.where(np.asarray(top) > -np.inf, np.asarray(want), -1), axis=-1)
    got = np.asarray(got)
    check(np.array_equal(np.sort(got, axis=-1), want), "sparse_cut vs lax.top_k: another set")
    check(all((np.diff(r[r >= 0]) > 0).all() for r in got), "sparse_cut: not in slot order")
    info["sparse_cut"] = [round(first, 3), round(steady, 5), 0.0]

    # the read of the chosen rows at the same shape: the kernel against the jax.numpy gather,
    # over the slots just chosen (one row of them short, so some are -1)
    rank, rope, heads = (512, 64, 128) if not REHEARSAL else (32, 8, 4)
    form = latent_attention.read_form(batch, capacity, k, rank, rope)
    check(form == "gather_kernel", f"sparse_read form {form!r}")
    latent = jnp.asarray(rng.standard_normal((batch, capacity, rank)), jnp.bfloat16)
    turned = jnp.asarray(rng.standard_normal((batch, capacity // 2, 2 * rope)), jnp.bfloat16)
    q_lat = jnp.asarray(rng.standard_normal((batch, heads, rank)) * 0.05, jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((batch, heads, rope)) * 0.05, jnp.float32)
    chosen = jnp.asarray(got)
    fused = jax.jit(lambda *a: latent_attention.latent_decode_attention(*a, 0.1))
    got, first, steady = first_and_steady(lambda: fused(q_lat, q_pe, latent, turned, chosen))
    os.environ["HEAT_TPU_PALLAS"] = "off"
    try:
        want = jax.jit(lambda *a: latent_attention.latent_decode_attention(*a, 0.1))(
            q_lat, q_pe, latent, turned, chosen)
    finally:
        os.environ["HEAT_TPU_PALLAS"] = KERNEL_MODE
    err = close(got, want, 1e-5, "sparse_read kernel vs the jax.numpy gather")
    info["sparse_read"] = [round(first, 3), round(steady, 5), err]
    return {"kernel": "[first_s, steady_s, max_err]", **info}


def st_brumby():
    """Brumby-14B-Base five layers deep at the published widths (the
    benchmark's configuration: 6.4 GB of seeded weights), on one device:
    prefill of 2 x 1,024 tokens in chunks, a save, four greedy steps through
    the state kernel, a rewind and the same four steps again, against the
    plain reference's full forward pass (attention form, float32)."""
    from jax.sharding import Mesh

    from heat_tpu.models import brumby
    from heat_tpu.parallel.mesh import MeshComm
    from perf.reference import brumby as reference

    if REHEARSAL:
        cfg = brumby.BrumbyConfig(vocab_size=96, hidden_size=64, intermediate_size=128,
                                  num_attention_heads=6, num_key_value_heads=2, head_dim=16,
                                  num_hidden_layers=3, dtype="float32")
        length, tol = 40, 1e-4
    else:
        cfg = brumby.BrumbyConfig(num_hidden_layers=5)
        length, tol = 1024, 2e-2
        check(_pallas_common.mode() == KERNEL_MODE, f"pallas mode {_pallas_common.mode()!r}")
    # a Mosaic kernel is a one-device program (the served models are not
    # sharded: PERF.md section 7)
    one = MeshComm(Mesh(np.array(jax.devices()[:1]), ("x",)), "x")
    model = brumby.Brumby(cfg, seed=7, comm=one)
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, (2, length)).astype(np.int32)
    session = model.session(2, length + 8)
    (first, saved), prefill_s = clocked(
        lambda: (session.prefill(tokens), session.save()))
    first_token = np.asarray(jnp.argmax(first.larray, -1))
    (chosen, logits), decode_s = clocked(lambda: session.decode(4))
    session.rewind(saved)
    again, again_logits = session.decode(4)
    check(np.array_equal(np.asarray(again.larray), np.asarray(chosen.larray))
          and np.array_equal(np.asarray(again_logits.larray), np.asarray(logits.larray)),
          "decode after a rewind does not repeat itself")
    rcfg = {k: getattr(cfg, k) for k in reference.SIZES}
    chosen = np.asarray(chosen.larray)
    worst = 0.0
    for b in range(2):
        seq = jnp.asarray(np.concatenate([tokens[b], [first_token[b]], chosen[b, :-1]]))
        want = reference.logits_at_end(rcfg, model.params, seq, 4)
        worst = max(worst, close(logits.larray[b], want, tol, "brumby logits vs reference"))
    held = session.cache_bytes()["state"]
    model.params = None
    return {"layers": cfg.num_hidden_layers, "prefill_s": round(prefill_s, 3),
            "decode4_first_s": round(decode_s, 3), "logits_err": worst, "state_bytes": held}


def st_deepseek():
    """DeepSeek-V3.2 as the benchmark's configuration cuts it (one dense and
    four expert layers at the published widths, experts 0-15 of 256 held, an
    eighth of the vocabulary: 9.3 GB of seeded weights), on one device:
    prefill of 2 x 4,096 tokens (so that the selection is a true choice: 4,096
    > 2,048), a save, four greedy steps, a rewind and the same four steps
    again, against the plain reference's full forward pass (per-head attention
    with the selection as a mask, float32).  The median position is held to
    the tolerance: a routing near-tie moves a single position by one expert's
    whole output (PERF.md section 2)."""
    from jax.sharding import Mesh

    from heat_tpu.models import deepseek
    from heat_tpu.parallel.mesh import MeshComm
    from perf.reference import deepseek as reference

    if REHEARSAL:
        cfg = deepseek.DeepSeekConfig(
            vocab_size=96, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            index_n_heads=16, index_head_dim=16, index_topk=12, n_routed_experts=8,
            num_experts_per_tok=2, n_group=4, topk_group=2, max_position_embeddings=512,
            rope_original=16, experts_held=(2, 2), vocab_held=48, dtype="float32")
        length, tol = 40, 1e-4
    else:
        cfg = deepseek.DeepSeekConfig(num_hidden_layers=5, first_k_dense_replace=1,
                                      experts_held=(0, 16), vocab_held=16160)
        length, tol = 4096, 3e-2
    one = MeshComm(Mesh(np.array(jax.devices()[:1]), ("x",)), "x")
    model = deepseek.DeepSeek(cfg, seed=11, comm=one)
    tokens = np.random.default_rng(11).integers(0, cfg.vocab_held, (2, length)).astype(np.int32)
    session = model.session(2, length + 8)
    form = latent_attention.selection_form(2, session.capacity, cfg.index_topk)
    check(form == "cut_kernel", f"the selection of a decode step is {form!r}, not the kernel")
    form = latent_attention.read_form(2, session.capacity, cfg.index_topk, cfg.kv_lora_rank,
                                      cfg.qk_rope_head_dim, jnp.dtype(cfg.dtype).itemsize,
                                      cfg.num_attention_heads)
    check(form == "gather_kernel", f"the read of a decode step is {form!r}, not the kernel")
    (first, saved), prefill_s = clocked(
        lambda: (session.prefill(tokens), session.save()))
    first_token = np.asarray(jnp.argmax(first.larray, -1))
    (chosen, logits), decode_s = clocked(lambda: session.decode(4))
    selection = np.asarray(model.last_selection)
    session.rewind(saved)
    again, again_logits = session.decode(4)
    check(np.array_equal(np.asarray(again.larray), np.asarray(chosen.larray))
          and np.array_equal(np.asarray(again_logits.larray), np.asarray(logits.larray)),
          "decode after a rewind does not repeat itself")
    check(selection.shape == (cfg.num_hidden_layers, 2, min(cfg.index_topk, session.capacity))
          and (selection >= 0).all() and (selection < length + 4).all(),
          "the last step's selection is not index_topk visible slots a layer and session")
    rcfg = {k: getattr(cfg, k) for k in reference.SIZES if hasattr(cfg, k)}
    rcfg["router_experts"] = cfg.n_routed_experts
    rcfg["experts_first"], rcfg["n_routed_experts"] = cfg.experts_held
    chosen = np.asarray(chosen.larray)
    errs, missed = [], 0.0
    for b in range(2):
        seq = jnp.asarray(np.concatenate([tokens[b], [first_token[b]], chosen[b, :-1]]))
        want = reference.forward(rcfg, model.params, seq, 4)
        got = np.asarray(logits.larray[b], np.float64)
        ref_logits = np.asarray(want["logits"], np.float64)
        check(np.isfinite(got).all(), "deepseek logits: non-finite values")
        errs.extend(np.linalg.norm(got - ref_logits, axis=-1) / np.linalg.norm(ref_logits, axis=-1))
        for layer in range(cfg.num_hidden_layers):
            read = np.zeros(length + 4, bool)
            read[selection[layer, b]] = True
            wanted = np.asarray(want["selected"][layer])
            missed = max(missed, float((wanted & ~read).sum() / wanted.sum()))
    median = float(np.median(errs))
    check(median <= tol, f"deepseek logits vs reference: median error {median:.3e} > {tol:.3e}")
    check(missed <= (0.0 if REHEARSAL else 0.05),
          f"deepseek selection: {missed:.4f} of the reference's positions not read")
    held = session.cache_bytes()["shared"]
    model.params = None
    return {"layers": cfg.num_hidden_layers, "prefill_s": round(prefill_s, 3),
            "decode4_first_s": round(decode_s, 3), "logits_err_median": median,
            "logits_err_worst": float(max(errs)), "selection_missed": missed,
            "cache_bytes": held, "prefill_attention": prefill_forms(cfg, session.capacity)}


def prefill_forms(cfg, capacity):
    """One short prefill chunk's attention (the last 256 positions of the
    session's capacity, all of the model's heads) through the kernel
    ``ht_sparse_prefill_attn`` and through the ``jax.numpy`` form, compared:
    tier-1 runs the kernel only through the interpreter."""
    chunk, heads = 256 if not REHEARSAL else 32, cfg.num_attention_heads
    widths = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(jax.random.key(13), 8)
    normal = lambda i, *shape: jax.random.normal(keys[i], shape, jnp.float32)  # noqa: E731
    pos0 = capacity - chunk
    scores = jnp.where(jnp.arange(capacity)[None, :] <= pos0 + jnp.arange(chunk)[:, None],
                       jnp.round(normal(0, chunk, capacity) * 4) / 4, -jnp.inf)
    args = (normal(1, chunk, heads, widths[0]), normal(2, chunk, heads, widths[1]),
            normal(3, capacity, widths[3]).astype(dtype),
            normal(4, capacity // latent_attention.ROPE_PACK,
                   latent_attention.ROPE_PACK * widths[1]).astype(dtype),
            scores, latent_attention.kth_largest(scores, cfg.index_topk), np.int32(pos0),
            (normal(5, heads, widths[0], widths[3]) * widths[3] ** -0.5).astype(dtype),
            (normal(6, heads, widths[3], widths[2]) * widths[3] ** -0.5).astype(dtype))
    got, info = {}, {}
    for how in (KERNEL_MODE, "off"):
        os.environ["HEAT_TPU_PALLAS"] = how
        try:
            form = latent_attention.prefill_form(chunk, capacity, heads, widths, dtype.itemsize)
            # a function of its own a form: the mode is read when the call is traced
            attend = jax.jit(lambda *a: latent_attention.latent_prefill_attention(
                *a, cfg.softmax_scale))
            got[form], first, steady = first_and_steady(lambda: attend(*args))
            info[form] = [round(first, 3), round(steady, 5)]
        finally:
            os.environ["HEAT_TPU_PALLAS"] = KERNEL_MODE
    check(set(got) == {"flash_kernel", "blockwise_jnp"}, f"prefill forms {sorted(got)}")
    info["max_err"] = close(got["flash_kernel"], got["blockwise_jnp"], 1e-3,
                            "prefill attention kernel vs jax.numpy form")
    return info


def st_glm_drafter():
    """GLM-4.7-Flash as the benchmark's configuration cuts it (one dense and
    four expert layers of 64 experts and the MTP module at the published
    widths, the whole vocabulary: 7.8 GB of the cell's seeded weights, whose
    drafts mostly stand), on one device: prefill of two sessions of 4,096 and
    3,001 tokens (positions of their own), a save, four speculative steps, a
    rewind and the same four steps again, against the plain reference's full
    forward pass and MTP pass (per-head attention over every earlier position,
    float32); the full read is the kernel ``ht_latent_stream``."""
    from heat_tpu.models import deepseek
    from perf import manifest
    from perf.drivers import spec_decode
    from perf.generators import glm_weights
    from perf.reference import glm as reference

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "perf", "configs", "glm-4.7-flash-5L-mtp-bf16.json")) as fh:
        config = json.load(fh)
    if REHEARSAL:
        config = manifest.merged(config, config["rehearse"])
        lengths, tol = [40, 29], 1e-4
    else:
        lengths, tol = [4096, 3001], 3e-2
    cfg = spec_decode.model_config(config)
    params = glm_weights.weights(config, jax.random.key(11), lambda leaf: leaf)
    model = deepseek.DeepSeek(cfg, params)
    steps = 4
    tokens = np.random.default_rng(11).integers(0, cfg.vocab_held, (2, max(lengths))).astype(np.int32)
    session = model.session(2, max(lengths) + 2 * steps)
    form = latent_stream.stream_form(session.capacity, cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    check(REHEARSAL or form == "stream_kernel", f"the full read of a decode step is {form!r}")
    (first, saved), prefill_s = clocked(lambda: (session.prefill(tokens, lengths=lengths),
                                                 session.save()))
    first_token = np.asarray(jnp.argmax(first.larray, -1))
    (emitted, logits), decode_s = clocked(lambda: session.decode(steps))
    emitted, logits = np.asarray(emitted.larray), np.asarray(logits.larray)
    accepted, counts = session.accepted.copy(), session.emitted.copy()
    drafts = np.asarray(model.last_drafts["logits"])
    session.rewind(saved)
    again, _ = session.decode(steps)
    check(np.array_equal(np.asarray(again.larray), emitted),
          "speculative decode after a rewind does not repeat itself")
    check(session.positions.tolist() == [n + int(c) for n, c in zip(lengths, counts)],
          "the sessions did not advance by what they emitted")
    rcfg = spec_decode.reference_config(config)
    errs, draft_errs = [], []
    for b, n in enumerate(lengths):
        count = int(counts[b])
        seq = jnp.asarray(np.concatenate([tokens[b, :n], [first_token[b]], emitted[b, :count]]))
        rows, mtp_rows, at = [], [], n
        for j in range(steps):
            rows += [(j, i, at + i) for i in range(1 + int(accepted[b, j]))]
            at += 1 + int(accepted[b, j])
            mtp_rows.append(at - 1)
        want = reference.forward(rcfg, model.params, seq, [r[2] for r in rows], mtp_rows)
        got = np.stack([logits[b, j, i] for j, i, _ in rows]).astype(np.float64)
        ref_logits = np.asarray(want["logits"], np.float64)
        check(np.isfinite(got).all(), "glm logits: non-finite values")
        errs.extend(np.linalg.norm(got - ref_logits, axis=-1) / np.linalg.norm(ref_logits, axis=-1))
        ref_drafts = np.asarray(want["draft_logits"], np.float64)
        draft_errs.extend(np.linalg.norm(drafts[b] - ref_drafts, axis=-1)
                          / np.linalg.norm(ref_drafts, axis=-1))
    median, draft_median = float(np.median(errs)), float(np.median(draft_errs))
    check(median <= tol, f"glm logits vs reference: median error {median:.3e} > {tol:.3e}")
    check(draft_median <= tol, f"glm drafts vs reference: median error {draft_median:.3e}")
    held = session.cache_bytes()["shared"]
    model.params = None
    return {"layers": cfg.num_hidden_layers, "prefill_s": round(prefill_s, 3),
            "decode4_first_s": round(decode_s, 3), "stream": form,
            "acceptance": float(accepted.mean()), "positions": session.positions.tolist(),
            "logits_err_median": median, "logits_err_worst": float(max(errs)),
            "draft_logits_err_median": draft_median, "cache_bytes": held}


def st_sdar_block_diffusion():
    """SDAR-30B-A3B-Chat at the benchmark configuration's widths (128 experts
    of 768 a layer, the whole vocabulary) and two layers, the cell's seeded
    weights, on one device: prefill of two sessions of 4,094 and 3,001 tokens
    (tails of 2 and 1 waiting in the first block), a save, three blocks of
    four positions (two denoising passes and a commit each), a rewind and the
    same blocks again, against the plain reference teacher-forced on the
    program's block states (the block-causal forward pass, float32); every
    pass reads through the kernel ``ht_decode_attention``, each session to its
    own block's end."""
    from heat_tpu.models import sdar
    from perf import manifest
    from perf.drivers import diffusion_decode
    from perf.generators import sdar_weights
    from perf.reference import sdar as reference

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "perf", "configs", "sdar-30b-a3b-5L-bd4-bf16.json")) as fh:
        config = json.load(fh)
    if REHEARSAL:
        config = manifest.merged(config, config["rehearse"])
        lengths, tol = [30, 21], 1e-4
    else:
        config["num_hidden_layers"] = 2
        lengths, tol = [4094, 3001], 3e-2
    cfg = diffusion_decode.model_config(config)
    params = sdar_weights.weights(config, jax.random.key(12), lambda leaf: leaf)
    model = sdar.SDAR(cfg, params)
    blocks, width = 3, cfg.block_length
    tokens = np.random.default_rng(12).integers(0, cfg.mask_token_id, (2, max(lengths)))
    session = model.session(2, max(lengths) + width * blocks)
    (_, saved), prefill_s = clocked(lambda: (session.prefill(tokens.astype(np.int32),
                                                             lengths=lengths), session.save()))
    (made, logits), decode_s = clocked(lambda: session.decode(blocks))
    made, logits, confidence = np.asarray(made.larray), np.asarray(logits.larray), session.confidence
    session.rewind(saved)
    again, _ = session.decode(blocks)
    check(np.array_equal(np.asarray(again.larray), made),
          "block diffusion after a rewind does not repeat itself")
    whole = [n - n % width for n in lengths]
    check(session.positions.tolist() == [w + width * blocks for w in whole],
          "the sessions did not advance by whole blocks from their prompts' whole blocks")
    rcfg = diffusion_decode.reference_config(config)
    errs = []
    for b, n in enumerate(lengths):
        before = reference.prefix(rcfg, model.params, jnp.asarray(tokens[b, :whole[b]]),
                                  session.capacity)
        for j in range(blocks):
            pos = whole[b] + j * width
            final = made[b, j * width:(j + 1) * width].copy()
            for i in range(max(0, min(width, n - pos))):
                final[i] = tokens[b, pos + i]
            fixed = np.isinf(confidence[b, j])
            for s in range(cfg.denoising_steps):
                state = np.where(fixed[s], final, cfg.mask_token_id).astype(np.int32)
                want = np.asarray(reference.block(rcfg, model.params, before, jnp.asarray(state),
                                                  pos)[0], np.float64)
                after = fixed[s + 1] if s + 1 < cfg.denoising_steps else np.ones(width, bool)
                for i in np.flatnonzero(after & ~fixed[s]):
                    got = logits[b, j * width + i].astype(np.float64)
                    check(np.isfinite(got).all(), "sdar logits: non-finite values")
                    errs.append(np.linalg.norm(got - want[i]) / np.linalg.norm(want[i]))
            _, kv = reference.block(rcfg, model.params, before, jnp.asarray(final), pos)
            before = reference.committed(before, kv, pos)
    median = float(np.median(errs))
    check(median <= tol, f"sdar logits vs reference: median error {median:.3e} > {tol:.3e}")
    held = session.cache_bytes()["shared"]
    model.params = None
    return {"layers": cfg.num_hidden_layers, "prefill_s": round(prefill_s, 3),
            "decode3_first_s": round(decode_s, 3), "positions": session.positions.tolist(),
            "logits_err_median": median, "logits_err_worst": float(max(errs)),
            "cache_bytes": held}


def st_fence():
    """ROADMAP S1: one chain of matmuls timed by block_until_ready and by a
    scalar readback.  If the two agree and both dwarf the enqueue-only
    time, block_until_ready synchronizes on this chip.  The operands keep
    the chain finite (b ~ N(0, 1/n)), and each chain length is warmed up
    first, so no compile and no non-finite guard replay is in a timing.
    Two chains: plain jax (the fence alone) and ht.matmul (the library,
    whose materialization has syncs of its own)."""
    n = SZ["matmul_n"]
    a = ht.random.randn(n, n, dtype=ht.bfloat16, split=0)
    b = ht.random.randn(n, n, dtype=ht.bfloat16, split=None) * (1.0 / n ** 0.5)
    ready((a, b))
    mm = jax.jit(jnp.matmul)

    def jax_chain(k):
        c = a.larray
        for _ in range(k):
            c = mm(c, b.larray)
        return c

    def ht_chain(k):
        c = a
        for _ in range(k):
            c = ht.matmul(c, b)
        return c.larray

    def timed_chain(chain, k, fence):
        t0 = time.perf_counter()
        arr = chain(k)
        enqueued = time.perf_counter() - t0
        if fence == "block_until_ready":
            jax.block_until_ready(arr)
        elif fence == "readback":
            check(np.isfinite(float(jnp.sum(arr.astype(jnp.float32)))), "chain went non-finite")
        return time.perf_counter() - t0, enqueued

    k1, k2 = SZ["fence_k"]
    out = {}
    for name, chain in (("jax", jax_chain), ("ht.matmul", ht_chain)):
        for k in (k1, k2):  # warm-up: compile this chain length, and the readback
            timed_chain(chain, k, "readback")
        for fence in ("block_until_ready", "readback"):
            (t1, _), (t2, enq) = timed_chain(chain, k1, fence), timed_chain(chain, k2, fence)
            out[f"{name}/{fence}"] = {
                f"k{k1}_s": round(t1, 5), f"k{k2}_s": round(t2, 5),
                "slope_s_per_matmul": round((t2 - t1) / (k2 - k1), 6),
                f"k{k2}_enqueue_only_s": round(enq, 5),
            }
    return out


def st_multichip():
    """Four chips: one pass of each schedule that is degenerate on one."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from heat_tpu.parallel.expert import moe_ffn
    from heat_tpu.parallel.pipeline import pipeline_apply, stack_stage_params
    from heat_tpu.parallel.sequence import sequence_parallel_attention

    devices = np.array(jax.devices())
    rng = np.random.default_rng(12)
    info = {}

    # ring matmul (parallel/overlap.py) against the GSPMD einsum
    m, k, n = SZ["ring_mm"]
    A = rng.standard_normal((m, k)).astype(np.float32)
    B = rng.standard_normal((k, n)).astype(np.float32)

    def mm(mode):
        overlap.set_mode(mode)
        try:
            with fusion.fuse(False):
                return ht.matmul(ht.array(A, split=0), ht.array(B, split=0))
        finally:
            overlap.set_mode(None)

    before = overlap.stats()["ring_calls"]
    ring, t_ring = clocked(mm, "ring")
    check(overlap.stats()["ring_calls"] > before, "ring matmul did not dispatch a ring")
    ref = jnp.matmul(jnp.asarray(A), jnp.asarray(B), precision=HI)
    info["ring_matmul"] = [round(t_ring, 3), close(ring.numpy(), ref, 2e-2, "ring matmul")]

    # int8 wire on a split 0 -> 1 resplit (core/wire.py)
    shape = SZ["wire_resplit"]
    host = rng.standard_normal(shape).astype(np.float32)
    prev = wire.set_mode("int8")
    try:
        telemetry.reset_group("wire")
        x = ht.array(host, split=0)
        (_, t_wire) = clocked(lambda: x.resplit_(1))
        st = wire.stats()
    finally:
        wire.set_mode(prev)
    check(st["quantized_dispatches"] > 0, "int8 wire: no quantized dispatch")
    check(st["bytes_wire"] * 3 <= st["bytes_logical"], f"int8 wire moved {st['bytes_wire']} of {st['bytes_logical']} B")
    err = float(np.abs(x.numpy() - host).max())
    check(err <= np.abs(host).max() / 254 * 1.01, f"int8 wire error {err}")
    info["wire_int8_resplit"] = [round(t_wire, 3), err]

    # MoE all_to_all over an expert-parallel mesh against the local path
    t, d, h = SZ["moe"]
    t = min(t, 4096) * NDEV
    x, gate, w_in, w_out = _moe_operands(t, d, h, 2 * NDEV, jnp.float32, 13)
    ep = Mesh(devices, ("ep",))
    # E = 2 * NDEV experts, k = 2: capacity_factor NDEV is dropless on both paths
    cf = float(NDEV)
    (y, aux), t_moe = clocked(lambda: moe_ffn(x, gate, w_in, w_out, k=2, capacity_factor=cf, mesh=ep, axis="ep"))
    check(float(aux["fraction_dropped"]) == 0.0, "ep MoE dropped tokens")
    y0, _ = moe_ffn(x, gate, w_in, w_out, k=2, capacity_factor=cf)
    info["moe_all_to_all"] = [round(t_moe, 3), close(y, y0, 2e-2, "ep moe_ffn vs local")]

    # ring attention, sequence sharded over the mesh
    b, hds, s, dh = SZ["sp_attn"]
    sp = Mesh(devices, ("sp",))
    q, kk, v = (jnp.asarray(rng.standard_normal((b, hds, s, dh)), jnp.float32) for _ in range(3))
    sh = NamedSharding(sp, P(None, None, "sp", None))
    qs, ks, vs = (jax.device_put(z, sh) for z in (q, kk, v))
    out, t_sp = clocked(lambda: sequence_parallel_attention(qs, ks, vs, sp, "sp", causal=True, strategy="ring"))
    ref = attention._attention_ref(
        q.reshape(b * hds, s, dh), kk.reshape(b * hds, s, dh), v.reshape(b * hds, s, dh),
        True, 1.0 / dh ** 0.5).reshape(b, hds, s, dh)
    info["ring_attention"] = [round(t_sp, 3), close(out, ref, 2e-2, "ring attention")]

    # pipeline schedule, one stage per chip
    rows, width = SZ["pipe"]
    pp = Mesh(devices, ("pp",))
    stages = [{"w": jnp.asarray(rng.standard_normal((width, width)) / np.sqrt(width), jnp.float32)}
              for _ in range(NDEV)]
    params = stack_stage_params(stages, pp)
    xb = jnp.asarray(rng.standard_normal((rows, width)), jnp.float32)
    out, t_pp = clocked(lambda: pipeline_apply(
        lambda p, z: jnp.tanh(jnp.matmul(z, p["w"], precision=HI)), params, xb, mesh=pp, n_micro=4))
    ref = xb
    for stg in stages:
        ref = jnp.tanh(jnp.matmul(ref, stg["w"], precision=HI))
    info["pipeline"] = [round(t_pp, 3), close(out, ref, 1e-3, "pipeline")]
    return {"pass": "[seconds incl. compile, max_err]", **info}


def verdict_counters():
    """Read the program's own counters; anything degraded is a failure."""
    bad = []
    reasons = fusion.cache_stats()["fallback_reasons"]
    for key in ("compile_error", "exec_error"):
        if reasons.get(key, 0):
            bad.append(f"fusion fallback {key}={reasons[key]}")
    tstats = transport.stats()
    if tstats["oom_retries"]:
        bad.append(f"transport oom_retries={tstats['oom_retries']}")
    fallbacks = [r for r in RESULTS if r.get("fallback_events")]
    for r in fallbacks:
        bad.append(f"telemetry fallback event(s) in stage {r['stage']}: {r['fallback_events'][:2]}")
    table = autotune.table()
    for key, e in table.items():
        for arm, durs in e["arms"].items():
            if any(not np.isfinite(v) for v in durs):
                bad.append(f"autotune arm {arm} of {e['desc']!r} recorded inf")
    say("counters", json.dumps({
        "fusion": {k: v for k, v in fusion.cache_stats().items()
                   if k in ("hits", "misses", "fallbacks", "fallback_reasons")},
        "transport_oom_retries": tstats["oom_retries"],
        "autotune": {e["desc"]: {"winner": e["winner"],
                                 "arms": {a: (round(min(d), 5) if d else None) for a, d in e["arms"].items()}}
                     for e in table.values()},
        "autotune_stats": autotune.stats(),
    }, default=str))
    return bad


def main():
    cache_dir = compile_cache.enable()
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # not installed as a distribution: report, do not guess
        libtpu = "unknown"
    say("device", json.dumps(DEVICE))
    say("versions", json.dumps({"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                                "libtpu": libtpu, "python": sys.version.split()[0]}))
    say("compile_cache_dir", cache_dir,
        "(from JAX_COMPILATION_CACHE_DIR)" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "(<checkout>/.jax_cache)",
        "entries_at_start:", len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)
    say("native.available():", native.available())
    say("x64:", jax.config.jax_enable_x64, "| autotune:", autotune.enabled(),
        "| pallas mode:", _pallas_common.mode(), "| memory_stats:", bytes_in_use())
    check_mode = _pallas_common.mode()
    # the flight recorder must see `fallback` events
    telemetry.set_level("events")

    t_all = time.perf_counter()
    stage("matmul_bf16", st_matmul)
    stage("resplit_roundtrip", st_resplit)
    stage("reshape_narrow_minor", st_reshape)
    stage("qr_tall_skinny", st_qr)
    stage("kmeans_fit", st_kmeans)
    stage("lasso_fit", st_lasso)
    stage("cdist", st_cdist)
    stage("dcsr_matvec", st_spmv)
    stage("resnet50_train", st_resnet)
    stage("serving_engine", st_serving)
    stage("flash_attention", st_flash_attention)
    stage("moe_ffn", st_moe)
    stage("pallas_kernels", st_kernels)
    stage("brumby_serve", st_brumby)
    stage("deepseek_serve", st_deepseek)
    stage("glm_drafter_serve", st_glm_drafter)
    stage("sdar_block_diffusion", st_sdar_block_diffusion)
    stage("fence_timing", st_fence)
    if NDEV > 1:
        stage("multichip_schedules", st_multichip)
    bad = verdict_counters()
    if check_mode != KERNEL_MODE:
        bad.append(f"Pallas mode was {check_mode!r}, not {KERNEL_MODE!r}")
    failed = [r["stage"] for r in RESULTS if not r["ok"]]
    say("total_s", round(time.perf_counter() - t_all, 1),
        "| cache entries at end:", len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)
    for line in bad:
        say("DEGRADED:", line)
    for name in failed:
        say("FAILED:", name)
    ok = not bad and not failed
    result = {"ok": ok, "device": DEVICE}
    if REHEARSAL:
        result = {"rehearsal": True, **result}
    if not ok:
        result["failed_stages"] = failed
        result["degraded"] = bad
    print(json.dumps(result), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
