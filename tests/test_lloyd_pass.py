"""The fused Lloyd pass (ISSUE 30): ``ops/lloyd_pass.py`` and its place in
``cluster/kmeans.py``.

Everything runs on the CPU mesh with the kernel in the Pallas interpreter
(``HEAT_TPU_PALLAS=interpret`` scoped per test): the kernel's sums, counts
and inertia against a plain float64 NumPy pass over operands rounded to
bfloat16; the masking of rows past ``n``; ties and empty clusters; a whole
``KMeans.fit`` against the same fit with the Pallas tier off; the shapes the
dispatch rule sends to the classic body; and that ``off`` leaves
``_lloyd_step`` the program it was.  Since ISSUE 33 the pass also writes the
labels on request: that output against the ``jax.numpy`` pass's argmin, the
pass without the request left as it was, ``labels_`` and ``predict`` of a
fused fit, and the labels function compiled for the chip."""

import os
import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh

import heat_tpu as ht
from heat_tpu.cluster import kmeans
from heat_tpu.core import telemetry
from heat_tpu.ops import lloyd_pass as lp
from heat_tpu.ops.cdist import cdist as ops_cdist
from heat_tpu.parallel.mesh import MeshComm


@pytest.fixture
def pallas(monkeypatch):
    """``pallas("interpret")`` sets the mode for the rest of the test."""
    return lambda value: monkeypatch.setenv("HEAT_TPU_PALLAS", value)


@pytest.fixture
def events():
    prev = telemetry.set_level("events")
    telemetry.clear_events()
    yield lambda name="kmeans.fit": [e for e in telemetry.events("span_end") if e["name"] == name]
    telemetry.clear_events()
    telemetry.set_level(prev)


def _bf16(a):
    return np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32), np.float64)


def _reference(x, c, n):
    """One pass in float64 over the operands the kernel multiplies:
    rows and centres rounded to bfloat16 in the products, the norms from
    the values as given."""
    x, c = np.asarray(jnp.asarray(x).astype(jnp.float32))[:n], np.asarray(jnp.asarray(c).astype(jnp.float32))
    xb, cb = _bf16(x), _bf16(c)
    m2 = (c.astype(np.float64) ** 2).sum(1)[None, :] - 2.0 * xb @ cb.T
    onehot = np.eye(c.shape[0])[m2.argmin(1)]
    inertia = np.maximum((x.astype(np.float64) ** 2).sum(1) + m2.min(1), 0.0).sum()
    return onehot.T @ xb, onehot.sum(0), inertia


def _rows(n, f, k, dtype, seed=0):
    rng = np.random.default_rng([seed, n, f, k])
    centres = 2.0 * rng.normal(size=(k, f))
    x = centres[rng.integers(0, k, n)] + rng.normal(size=(n, f))
    return jnp.asarray(x, dtype), jnp.asarray(centres + 0.1 * rng.normal(size=(k, f)), dtype)


def _close(got, want, n, f):
    sums, counts, inertia = (np.asarray(g, np.float64) for g in got)
    np.testing.assert_array_equal(counts, want[1])
    # float32 accumulation of up to n products of bfloat16 values
    np.testing.assert_allclose(sums, want[0], rtol=0, atol=2e-6 * max(n, 64) ** 0.5 * np.abs(want[0]).max())
    np.testing.assert_allclose(inertia, want[2], rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("k", [1, 2, 8, 37])
@pytest.mark.parametrize("f", [8, 20, 64, 100])
@pytest.mark.parametrize("n", [300, 1337], ids=["under_a_tile", "ends_in_a_tile"])
def test_pass_matches_float64_reference(n, f, k, dtype):
    """Tiles of 512 rows: 300 rows are less than one, 1,337 end 313 rows
    into the third."""
    x, c = _rows(n, f, k, dtype)
    got = lp._pass_pallas(x.T, c, n, interpret=True, chunk=256, chunks=2)
    _close(got, _reference(x, c, n), n, f)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_default_tile_and_the_jnp_fallback_agree(dtype, pallas):
    """The sizes the library runs with, over more than one tile, through
    the public entry in both modes."""
    n, f, k = 140_000, 8, 3
    x, c = _rows(n, f, k, dtype)
    want = _reference(x, c, n)
    for how in ("interpret", "off"):
        pallas(how)
        _close(lp.lloyd_pass(x.T, c, n), want, n, f)


@pytest.mark.parametrize("n", [1, 255, 256, 700, 1024, 1500], ids=lambda n: f"n{n}")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_rows_past_n_change_nothing(n, dtype):
    """1,536 slots in tiles of 512: the rows end inside a tile, at a tile's
    edge, or whole tiles before the end; every slot past them holds NaN."""
    slots, f, k = 1536, 20, 4
    x, c = _rows(slots, f, k, dtype)
    want = lp._pass_pallas(x[:n].T, c, n, interpret=True, chunk=256, chunks=2)
    poisoned = x.at[n:].set(jnp.nan)
    got = lp._pass_pallas(poisoned.T, c, n, interpret=True, chunk=256, chunks=2)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert float(np.asarray(got[1]).sum()) == n


def test_exact_ties_go_to_the_lowest_index():
    x, c = _rows(600, 20, 3, jnp.float32)
    twice = jnp.concatenate([c[:1], c, c[1:2]])  # 0 = 1 and 2 = 4
    sums, counts, _ = lp._pass_pallas(x.T, twice, 600, interpret=True, chunk=256, chunks=2)
    base = lp._pass_pallas(x.T, c, 600, interpret=True, chunk=256, chunks=2)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(base[1])[[0, 0, 1, 2, 1]] * [1, 0, 1, 1, 0])
    np.testing.assert_array_equal(np.asarray(sums)[[0, 2, 3]], np.asarray(base[0]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_empty_cluster_counts_zero_and_keeps_its_centre(dtype, pallas):
    pallas("interpret")
    x, c = _rows(900, 20, 3, dtype)
    far = jnp.concatenate([c, jnp.full((1, 20), 300.0, dtype)])
    _, counts, _ = lp.lloyd_pass(x.T, far, 900)
    assert float(counts[3]) == 0.0 and float(counts.sum()) == 900.0
    new, shift, _ = kmeans._lloyd_step(x, far, 4, fused=(900, None, None))
    np.testing.assert_array_equal(np.asarray(new[3], np.float32), np.asarray(far[3], np.float32))
    assert np.isfinite(float(shift)) and new.dtype == far.dtype


# ------------------------------------------------------ the labels it writes

_SMALL = dict(interpret=True, chunk=256, chunks=2)  # tiles of 512 rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("k", [1, 8, 37])
@pytest.mark.parametrize("f", [8, 64, 100])
@pytest.mark.parametrize("n", [512, 513, 100], ids=["a_whole_tile", "one_past_a_tile", "under_a_chunk"])
def test_labels_are_the_argmin_of_the_scores(n, f, k, dtype):
    """The fourth result against ``jnp.argmin`` of the ``jax.numpy`` pass's
    scores (``mode()`` = ``off`` is the arithmetic's reference), against the
    counts the same run returns, and the three other results unmoved by
    the request."""
    x, c = _rows(n, f, k, dtype)
    plain = lp._pass_pallas(x.T, c, n, **_SMALL)
    got = lp._pass_pallas(x.T, c, n, labels=True, **_SMALL)
    want = lp._pass_jnp(x.T, c, n, labels=True)
    assert len(plain) == 3 and len(got) == 4 == len(want)
    assert got[3].shape == (n,) == want[3].shape and got[3].dtype == jnp.int32 == want[3].dtype
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))
    np.testing.assert_array_equal(np.bincount(np.asarray(got[3]), minlength=k), np.asarray(got[1]))
    for a, b in zip(plain, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_labels_through_the_public_entry_in_both_modes(dtype, pallas):
    """The library's own tile sizes over more than one tile; near-ties may
    fall either way between two tilings of one product, so the labels are
    held to the float64 scores: none worse than the best by a rounding."""
    n, f, k = 140_000, 8, 3
    x, c = _rows(n, f, k, dtype)
    xf, cf = np.asarray(x.astype(jnp.float32), np.float64), np.asarray(c.astype(jnp.float32), np.float64)
    m2 = (cf ** 2).sum(1)[None, :] - 2.0 * _bf16(x) @ _bf16(c).T
    for how in ("interpret", "off"):
        pallas(how)
        *three, numbers = lp.lloyd_pass(x.T, c, n, labels=True)
        assert len(lp.lloyd_pass(x.T, c, n)) == 3 and numbers.shape == (n,) and numbers.dtype == jnp.int32
        numbers = np.asarray(numbers)
        assert numbers.min() >= 0 and numbers.max() < k
        np.testing.assert_array_equal(np.bincount(numbers, minlength=k), np.asarray(three[1]))
        gap = m2[np.arange(n), numbers] - m2.min(1)
        assert gap.max() <= 1e-5 * (xf ** 2).sum(1).max() and (numbers != m2.argmin(1)).mean() < 1e-4


def test_labels_of_exact_ties_are_the_lowest_index(pallas):
    x, c = _rows(600, 20, 3, jnp.float32)
    twice = jnp.concatenate([c[:1], c, c[1:2]])  # 0 = 1 and 2 = 4
    base = np.asarray(lp._pass_pallas(x.T, c, 600, labels=True, **_SMALL)[3])
    for how in ("interpret", "off"):
        pallas(how)
        got = np.asarray(lp.lloyd_pass(x.T, twice, 600, labels=True)[3])
        np.testing.assert_array_equal(got, np.array([0, 2, 3])[base])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_row_of_nan_is_labelled_in_range(dtype, pallas):
    """Scores that are all NaN equal no minimum; the row gets 0, as
    ``jnp.argmin`` gives, never the number of a padding cluster, and its
    neighbours keep their labels."""
    n, k = 700, 5
    x, c = _rows(n, 20, k, dtype)
    clean = np.asarray(lp._pass_pallas(x.T, c, n, labels=True, **_SMALL)[3])
    rows = np.array([0, 255, 256, 511, 512, 699])
    poisoned = x.at[rows].set(jnp.nan)
    got = np.asarray(lp._pass_pallas(poisoned.T, c, n, labels=True, **_SMALL)[3])
    pallas("off")
    np.testing.assert_array_equal(got, np.asarray(lp.lloyd_pass(poisoned.T, c, n, labels=True)[3]))
    assert (got[rows] == 0).all() and got.max() < k
    keep = np.setdiff1d(np.arange(n), rows)
    np.testing.assert_array_equal(got[keep], clean[keep])


@pytest.mark.parametrize("n", [1, 255, 256, 700, 1024, 1500], ids=lambda n: f"n{n}")
def test_rows_past_n_change_no_valid_label(n):
    slots, f, k = 1536, 20, 4
    x, c = _rows(slots, f, k, jnp.float32)
    want = lp._pass_pallas(x[:n].T, c, n, labels=True, **_SMALL)[3]
    got = lp._pass_pallas(x.at[n:].set(jnp.nan).T, c, n, labels=True, **_SMALL)[3]
    assert got.shape == (slots,)
    np.testing.assert_array_equal(np.asarray(got[:n]), np.asarray(want))
    assert int(got.min()) >= 0 and int(got.max()) < 16  # whatever they hold, a number the kernel has


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_without_the_request_the_kernel_is_the_parents(dtype):
    """Three results of the sizes they had, the cost estimate the parent
    gave, no ref more in the kernel's body (its jaxpr read the same as commit
    f474e9b's at six shapes, PR 33); with the request one ``(1, n)`` int32
    result and its 4 n bytes more."""
    n, f, k, kp = 20_011, 100, 37, 48
    xt, c = jax.ShapeDtypeStruct((f, n), dtype), jax.ShapeDtypeStruct((k, f), dtype)

    def call(labels):
        with jax.enable_x64(False):
            jaxpr = jax.make_jaxpr(lambda xt, c: lp._pass_pallas(xt, c, n, interpret=False, labels=labels))(xt, c)
        (eqn,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
        return eqn.params

    plain, asked = call(False), call(True)
    three = [(kp, f), (kp, 128), (1, 128)]
    assert [a.shape for a in plain["out_avals"]] == three
    assert [a.shape for a in asked["out_avals"]] == three + [(1, n)] and asked["out_avals"][3].dtype == jnp.int32
    nbytes = n * f * jnp.dtype(dtype).itemsize
    assert plain["cost_estimate"] == pl.CostEstimate(
        flops=4 * n * f * kp + 2 * n * f, bytes_accessed=nbytes, transcendentals=0)
    assert asked["cost_estimate"] == pl.CostEstimate(
        flops=4 * n * f * kp + 2 * n * f, bytes_accessed=nbytes + 4 * n, transcendentals=0)
    # scalar prefetch, three operands, the results, three accumulators
    assert len(plain["jaxpr"].invars) == 10 and len(asked["jaxpr"].invars) == 11
    assert str(asked["jaxpr"]).count("<-") == str(plain["jaxpr"]).count("<-") + 2  # a store in each kind of tile


# ---------------------------------------------------------------- KMeans.fit

def _blobs(n, f, k, seed=1):
    """Well separated blobs of values bfloat16 holds exactly, row r in blob
    r % k: rounding the rows changes nothing, so the fused body and the
    classic one (float32 products on the CPU) assign alike."""
    rng = np.random.default_rng(seed)
    centres = np.round(8.0 * rng.normal(size=(k, f)))
    x = centres[np.arange(n) % k] + np.round(4.0 * rng.normal(size=(n, f))) / 8.0
    return x.astype(np.float32)


def _one_device_comm():
    return MeshComm(Mesh(np.array(jax.devices()[:1]), ("x",)), split_axis="x")


def _fit(xn, k, comm=None, split=0, max_iter=12, tol=1e-9):
    """Centres, ``labels_``, iterations, inertia, and ``predict`` of the
    rows shifted a little (another assignment of another array)."""
    kw = {} if comm is None else {"comm": comm}
    x = ht.array(xn, split=split, **kw)
    init = ht.array(xn[:k] + 0.25, split=None, **kw)
    est = ht.cluster.KMeans(n_clusters=k, init=init, max_iter=max_iter, tol=tol).fit(x)
    again = est.predict(ht.array(xn[::-1] + 0.125, split=split, **kw))
    for labels in (est.labels_, again):
        assert labels.shape == (len(xn), 1) and labels.split == split
    assert est.labels_.dtype == again.dtype and again.parray.shape == est.labels_.parray.shape
    return (np.asarray(est.cluster_centers_.larray), np.asarray(est.labels_.larray).ravel(),
            est.n_iter_, float(est.inertia_), np.asarray(again.larray).ravel(),
            (type(est.labels_), est.labels_.dtype, est.labels_.parray.sharding))


@pytest.mark.parametrize("where,n", [("one_device", 2001), ("split0_mesh", 2048), ("split0_uneven", 2001)])
def test_fit_fused_agrees_with_classic(where, n, pallas, events):
    """Centres to 1e-5, the same labels and iterations, on one device and
    with the rows split over the mesh (2,001 rows leave the last shard
    short: the physical array's padding is masked by row number).  The
    labels, ``fit``'s and ``predict``'s, are the kernel's where the loop is
    (``assign`` on ``kmeans.labels``), a concrete array of the type and
    placement the lazy path gives."""
    comm = _one_device_comm() if where == "one_device" else None
    xn, k = _blobs(n, 20, 5), 5
    pallas("off")
    c0, l0, it0, in0, p0, kind0 = _fit(xn, k, comm)
    pallas("interpret")
    c1, l1, it1, in1, p1, kind1 = _fit(xn, k, comm)
    assert [e["lloyd"] for e in events()] == ["classic", "fused"]
    assert [e["assign"] for e in events("kmeans.labels")] == ["classic"] * 2 + ["fused"] * 2
    np.testing.assert_allclose(c1, c0, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(l1, l0)
    np.testing.assert_array_equal(p1, p0)
    np.testing.assert_array_equal(l0, np.arange(n) % k)
    np.testing.assert_array_equal(p0, np.arange(n)[::-1] % k)
    assert kind1[0] is ht.DNDarray and kind1[1] == kind0[1] and kind1[2].is_equivalent_to(kind0[2], 2)
    assert it1 == it0 and 1 < it0 < 12
    # not compared with the classic body's: here that multiplies in float32,
    # while the kernel's cross term sees the centres rounded to bfloat16 (as
    # the classic body's does on a TPU); the kernel's own tests hold it
    assert np.isfinite(in1) and in1 > 0
    np.testing.assert_allclose(c0, np.stack([xn[j::k].mean(0) for j in range(k)]), atol=1e-4)


@pytest.mark.parametrize("why", ["f_multiple_of_128", "k_over_128", "split1", "replicated_on_a_mesh", "integer_rows"])
def test_shapes_the_rule_rejects_run_the_classic_body(why, pallas, events):
    pallas("interpret")
    n, f, k, split, dtype = 640, 20, 3, 0, np.float32
    if why == "f_multiple_of_128":
        f = 128
    elif why == "k_over_128":
        n, k = 1290, 129
    elif why == "split1":
        f, split = 24, 1
    elif why == "replicated_on_a_mesh":
        split = None
    else:
        dtype = np.int32
    xn = _blobs(n, f, k).astype(dtype)
    x = ht.array(xn, split=split)
    assert kmeans._fused_rows(x, k) is None
    est = ht.cluster.KMeans(n_clusters=k, init=ht.array(xn[:k], split=None), max_iter=3, tol=-1.0).fit(x)
    assert [e["lloyd"] for e in events()] == ["classic"]
    assert est.n_iter_ == 3


def test_the_rule_accepts_what_it_says(pallas):
    pallas("interpret")
    rows = lambda shape, dtype=jnp.float32: jnp.zeros(shape, dtype)  # noqa: E731
    assert lp.accepts(rows((10, 64)), 8) and lp.accepts(rows((10, 100), jnp.bfloat16), 128)
    assert lp.accepts(rows((10, 8)), 1) and lp.accepts(rows((10, 129)), 1) and lp.accepts(rows((10, 511)), 1)
    for shape, dtype, k in [((10, 128), jnp.float32, 8), ((10, 256), jnp.float32, 8),
                            ((10, 64), jnp.float32, 129), ((10, 520), jnp.float32, 8),
                            ((10, 7), jnp.float32, 2), ((10, 4), jnp.bfloat16, 2),
                            ((10, 64), jnp.float64, 8), ((10, 64), jnp.int32, 8),
                            ((10, 4, 4), jnp.float32, 8), ((10,), jnp.float32, 8)]:
        assert not lp.accepts(rows(shape, dtype), k)
    # compiled for the device, the array's own layout is asked: the CPU
    # keeps rows major, which is not the orientation the kernel reads
    pallas("tpu")
    assert not lp.accepts(rows((10, 64)), 8)
    x = ht.array(_blobs(64, 20, 3), split=0)
    assert kmeans._fused_rows(x, 3) is None
    pallas("off")
    assert kmeans._fused_rows(x, 3) is None
    pallas("interpret")
    assert kmeans._fused_rows(x, 3) == (64, x.comm.mesh, x.comm.split_axis)
    one = ht.array(_blobs(64, 20, 3), split=0, comm=_one_device_comm())
    assert kmeans._fused_rows(one, 3) == (64, None, None)


@partial(jax.jit, static_argnames=("k",))
@telemetry.module_name("ht_lloyd_step")
def _parents_lloyd_step(x, centers, k: int):
    """``_lloyd_step`` as it stood before the fused pass (commit 6bc6f85)."""
    with jax.named_scope("ht.kmeans.assign"):
        d2 = ops_cdist(x, centers, sqrt=False)
        labels = jnp.argmin(d2, axis=1)
    with jax.named_scope("ht.kmeans.update"):
        onehot = (labels[:, None] == jnp.arange(k)[None, :]).astype(x.dtype)
        counts = jnp.sum(onehot, axis=0, dtype=jnp.float32)
        sums = jax.lax.dot_general(
            onehot, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1)[:, None], centers.astype(jnp.float32)
        ).astype(centers.dtype)
        shift = jnp.sum((new_centers - centers).astype(jnp.float32) ** 2)
    with jax.named_scope("ht.kmeans.assign"):
        inertia = jnp.sum(jnp.min(d2, axis=1))
    return new_centers, shift, inertia


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_off_leaves_the_parents_program(dtype, pallas):
    """With the Pallas tier off the step traces to the jaxpr it had, and
    the loop around it lowers to the same module text."""
    pallas("off")
    x, c = _rows(256, 20, 3, dtype)
    now = jax.make_jaxpr(lambda x, c: kmeans._lloyd_step(x, c, 3))(x, c)
    then = jax.make_jaxpr(lambda x, c: _parents_lloyd_step(x, c, 3))(x, c)
    assert str(now) == str(then)
    assert "lloyd_pass" not in kmeans._lloyd_loop.lower(x, c, 3, 5, 0.0).as_text()


# ------------------------------------------------- for the chip, without it

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("n,f,k", [(50_000, 64, 8), (5_000, 8, 1), (20_011, 100, 37), (9_000, 500, 128), (300, 20, 3)],
                         ids=lambda v: str(v))
def test_the_kernel_lowers_for_tpu(n, f, k, dtype):
    """The Pallas -> Mosaic lowering runs here for platform ``tpu`` and
    raises on an illegal block, a 64-bit constant or a primitive it lacks
    (the Mosaic compiler proper runs on the chip: ``chip_smoke.py``)."""
    xt, c = jax.ShapeDtypeStruct((f, n), dtype), jax.ShapeDtypeStruct((k, f), dtype)
    with jax.enable_x64(False):
        jax.jit(lambda xt, c: lp._pass_pallas(xt, c, n, interpret=False)).trace(xt, c).lower(
            lowering_platforms=("tpu",))


def test_the_loop_on_a_mesh_lowers_for_tpu(pallas):
    """GSPMD cannot partition a Mosaic call: the pass runs per shard under
    ``shard_map``, and the whole loop lowers for the 8-device mesh."""
    pallas("tpu")
    comm = ht.get_comm()
    x = jax.ShapeDtypeStruct((4096, 20), jnp.float32, sharding=comm.sharding(0, 2))
    c = jax.ShapeDtypeStruct((3, 20), jnp.float32, sharding=comm.sharding(None, 2))
    with jax.enable_x64(False):
        text = kmeans._lloyd_loop.trace(
            x, c, 3, 5, 0.0, fused=(4093, comm.mesh, comm.split_axis)
        ).lower(lowering_platforms=("tpu",)).as_text()
    # one psum of the three results: an all_reduce each here, which XLA
    # combines (one all-reduce in the loop compiled for four v5e chips, PR 30)
    assert "tpu_custom_call" in text and text.count("all_reduce") == 3


def test_the_labels_on_a_mesh_lower_for_tpu_without_a_collective(pallas):
    """The kernel per shard, its labels out along the split axis: nothing
    crosses the mesh."""
    pallas("tpu")
    comm = ht.get_comm()
    x = jax.ShapeDtypeStruct((4096, 20), jnp.float32, sharding=comm.sharding(0, 2))
    c = jax.ShapeDtypeStruct((3, 20), jnp.float32, sharding=comm.sharding(None, 2))
    with jax.enable_x64(False):
        lowered = kmeans._labels_of_rows.trace(
            x, c, fused=(4093, comm.mesh, comm.split_axis)).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert "tpu_custom_call" in text and lowered.out_info.shape == (4096, 1)
    assert not re.search(r"all_reduce|all_gather|all_to_all|collective_permute|reduce_scatter", text)


@pytest.fixture(scope="module")
def v5e():
    """One described (not attached) v5e chip: the TPU's compiler runs here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no compiler for the chip in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_compiled_loop_keeps_nothing_the_size_of_the_rows(dtype, v5e, pallas):
    """The benchmark cell's shape compiled for the chip: the rows are read
    where they lie (the transpose is a bitcast), so the program holds no
    temporary worth naming and no operation but the kernel touches an array
    of ``n`` elements or more."""
    pallas("tpu")
    n, f, k = 20_000_000, 64, 8
    x = jax.ShapeDtypeStruct((n, f), dtype, sharding=v5e)
    c = jax.ShapeDtypeStruct((k, f), dtype, sharding=v5e)
    with jax.enable_x64(False):
        compiled = kmeans._lloyd_loop.lower(x, c, k, 30, -1.0, fused=(n, None, None)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    made = re.findall(r"= \w+\[([\d,]+)\]\S* (?!parameter|get-tuple-element|bitcast)[\w\-]+\(", text)
    sizes = [int(np.prod([int(s) for s in d.split(",") if s])) for d in made]
    assert sizes and max(sizes) < n


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_compiled_labels_hold_no_distances(dtype, v5e, pallas):
    """The labels function at the benchmark cell's shape, compiled for the
    chip: one kernel, which alone touches the rows; the ``(n, 1)`` result is
    the kernel's own ``(1, n)`` bytes (the runtime lays it rows-minor, so the
    reshape is a bitcast); no ``(n, k)`` array, no temporary worth naming."""
    pallas("tpu")
    n, f, k = 20_000_000, 64, 8
    x = jax.ShapeDtypeStruct((n, f), dtype, sharding=v5e)
    c = jax.ShapeDtypeStruct((k, f), dtype, sharding=v5e)
    with jax.enable_x64(False):
        compiled = kmeans._labels_of_rows.lower(x, c, fused=(n, None, None)).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1 << 20 and memory.output_size_in_bytes == 4 * n
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    made = re.findall(r"= \w+\[([\d,]+)\]\S* (?!parameter|get-tuple-element|bitcast)[\w\-]+\(", text)
    sizes = [int(np.prod([int(s) for s in d.split(",") if s])) for d in made]
    assert sizes and max(sizes) < n
    rows = re.compile(r"\w+\[(20000000,64|64,20000000)\]")
    users = {m.group(1) for line in text.splitlines() if rows.search(line.split("=", 1)[-1])
             for m in [re.search(r"= \S+ ([\w\-]+)\(", line)] if m}
    assert users <= {"parameter", "bitcast", "custom-call"}, users


def test_the_retention_state_is_stepped_and_restored_in_place(v5e, pallas):
    """Here because one file, hence one process, may describe the chip (the
    ``on-chip-measurement`` guide): the decode step of a power-retention layer
    (``ops/power_retention.py``) and the copy that a session's ``rewind`` makes
    (``models/session.py``), at the benchmark cell's shapes, compiled for the
    chip: the state is updated where it lies and the saved state is copied
    into the live state's own buffers, with no temporary the size of either."""
    from heat_tpu.models import session
    from heat_tpu.ops import power_retention as pr
    pallas("tpu")
    batch, heads, group, d = 16, 8, 5, 128
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e)  # noqa: E731
    S, z = f32(batch, heads, d, pr.state_rows(d)), f32(batch, heads, pr.feature_blocks(d), d)
    held = 4 * batch * heads * (d + 1) * pr.state_rows(d)
    with jax.enable_x64(False):
        step = jax.jit(pr.retention_step, donate_argnums=(0, 1)).lower(
            S, z, f32(batch, heads, group, d), f32(batch, heads, d), f32(batch, heads, d),
            f32(batch, heads)).compile()
        token = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=v5e)
        tree = (token, {"S": (S, S), "z": (z, z)})
        restore = session._restored.lower(tree, tree).compile()
    for compiled, aliased, kernels in ((step, held, 1), (restore, 2 * 4 * S.size, 2)):
        memory = compiled.memory_analysis()
        assert memory.alias_size_in_bytes >= aliased
        assert memory.temp_size_in_bytes < 1 << 20
        assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == kernels
