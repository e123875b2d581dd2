"""One Lloyd iteration's pass over the rows: assign and accumulate, fused.

``lloyd_pass(xt, centers, nvalid)`` streams the rows once and returns what
the centre update needs and nothing of size ``n``: the per-cluster sums
``(k, f)``, the counts ``(k,)`` and the inertia, all float32, for the
assignment of every valid row to its nearest centre.  On request
(``labels=True``) the same pass also writes that assignment, an int32 a
row, laid along the lanes as the rows are: a fit's labels cost one more read
of the rows and no distances (``cluster/kmeans.py:_labels_of_rows``).

The rows come **transposed**, ``xt`` of shape ``(f, n)``: a tall ``(n, f)``
array whose width is no multiple of 128 lies rows-minor on the TPU (the
rows run along the lanes), so its transpose is the same bytes and the
kernel reads the array where it lies; handing Mosaic the logical ``(n, f)``
array would make XLA build a lane-padded copy of all of it.

Per tile of ``tile`` rows, in VMEM: the tile and the centres rounded to
bfloat16, the cross term on the MXU with float32 accumulation,
``m2 = |c|^2 - 2 cross`` (``|c|^2`` in float32 from the centres as given),
the argmin over the clusters with ties to the lowest index, the one-hot
sums of the rounded rows on the MXU, ``|x|^2`` in float32 from the rows as
stored and ``sum(max(|x|^2 + min m2, 0))``.  These are the operands of the
``jax.numpy`` Lloyd step at JAX's default TPU matmul precision; the
rounding happens in VMEM instead of in a stored copy.  Rows at or past
``nvalid`` are masked by their number, so the last tile needs no padding
and whatever lies past the end (NaN included) changes nothing.

Dispatch: :func:`accepts` says whether the kernel takes an array, by what
the code can see in it; :func:`lloyd_pass` runs the Pallas kernel where
``mode()`` is ``tpu`` or ``interpret`` and the same arithmetic in
``jax.numpy`` where it is ``off``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import LANE
from ._pallas_common import mode as _mode

__all__ = ["accepts", "lloyd_pass"]

# typed constants: Mosaic takes no 64-bit constant, and a Python float is
# one where x64 is on (ops/decode_attention.py)
_ZERO, _TWO, _INF = np.float32(0.0), np.float32(2.0), np.float32(np.inf)

MAX_K, MIN_F, MAX_F = 128, 8, 512  # past these the step is a real GEMM: XLA's
# A grid step fetches eight chunks of about 512 KiB of rows and folds them
# one by one.  A chunk's fold is a chain (product, argmin, product) of about
# 0.3 us whatever its size, which has to stay under its fetch (0.7 us at
# 750 GB/s): chunks of 512 rows of 64 float32 ran at 9.2-10.8 ms a pass over
# 2e7 rows, of 1,024 and more at the fetch's 6.82 (my chip run, PR 30)
_CHUNK_BYTES, _CHUNKS = 1 << 19, 8


def accepts(rows, k: int) -> bool:
    """Whether the kernel takes the array ``rows`` against ``k`` centres, by
    ``mode()`` and by what the array shows: 2-D float32 or bfloat16; ``k``
    and the width small enough that centres, scores and accumulators sit in
    VMEM and the pass is bound by the fetch; a width of at least one float32
    sublane tile (Mosaic refuses bfloat16 rows 2 to 4 wide and any 1 wide)
    that is no multiple of 128; and, on the device, the rows-minor layout,
    the one orientation the kernel reads in place.  The runtime lays a tall
    array rows-minor where padding its width to 128 lanes would waste much,
    by a rule of its own (widths 8 to 120, 129, 192, 257, 500 and 504 are,
    127, 250, 384, 510 and 511 are not: compiled for a v5e, PR 30), so the
    array's own format is asked; the interpreter has no device layout and
    takes any."""
    how = _mode()
    if how == "off" or rows.ndim != 2 or rows.shape[0] < 1:
        return False
    if jnp.dtype(rows.dtype) not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    f = rows.shape[1]
    if not (1 <= k <= MAX_K and MIN_F <= f <= MAX_F and f % LANE != 0):
        return False
    return how == "interpret" or tuple(rows.format.layout.major_to_minor) == (1, 0)


def _lane_sum(a, width):
    """``(r, width) -> (r, 128)``: the lane tiles of ``a`` added up, whole
    vector registers throughout; the 128 partial sums are added once, at
    the end of the pass."""
    out = a[:, :LANE]
    for j in range(1, width // LANE):
        out = out + a[:, j * LANE:(j + 1) * LANE]
    return out


def _pass_kernel(nv_ref, x_ref, cb_ref, cn_ref, sums_ref, counts_ref, inertia_ref,
                 *refs, tile, chunk):
    *labels_ref, acc_s, acc_c, acc_i = refs  # with the request for labels their ref comes between
    i = pl.program_id(0)
    kp = cb_ref.shape[0]

    @pl.when(i == 0)
    def _():
        acc_s[...] = jnp.zeros_like(acc_s)
        acc_c[...] = jnp.zeros_like(acc_c)
        acc_i[...] = jnp.zeros_like(acc_i)

    cb, cn = cb_ref[...], cn_ref[...]
    nvalid = nv_ref[0]
    first = i * np.int32(tile)
    cluster = jax.lax.broadcasted_iota(jnp.int32, (kp, chunk), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def fold(j, carry, masked):
        s, c, d = carry
        at = pl.multiple_of(j * np.int32(chunk), chunk)
        x = x_ref[:, pl.ds(at, chunk)]
        if masked:
            ok = first + at + lane < nvalid
            x = jnp.where(ok, x, jnp.zeros_like(x))
        xb = x.astype(jnp.bfloat16)
        cross = jax.lax.dot_general(
            cb, xb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m2 = cn - _TWO * cross
        best = jnp.min(m2, axis=0, keepdims=True)
        # the lowest cluster number among the minima, as jnp.argmin
        number = jnp.min(jnp.where(m2 == best, cluster, np.int32(kp)), axis=0, keepdims=True)
        if labels_ref:
            # scores that are all NaN equal no minimum: 0, as jnp.argmin gives
            labels_ref[0][:, pl.ds(at, chunk)] = jnp.where(number < np.int32(kp), number, np.int32(0))
        hit = cluster == number
        if masked:
            hit = hit & ok
        # contracting the lane axis of both, the q k^T form: as (f, chunk) .
        # (kp, chunk)^T the pass read 6.997 ms where this reads 6.82
        s = s + jax.lax.dot_general(
            hit.astype(jnp.bfloat16), xb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        c = c + _lane_sum(hit.astype(jnp.float32), chunk)
        xf = x.astype(jnp.float32)
        dist = jnp.maximum(jnp.sum(xf * xf, axis=0, keepdims=True) + best, _ZERO)
        if masked:
            dist = jnp.where(ok, dist, _ZERO)
        d = d + _lane_sum(dist, chunk)
        return s, c, d

    def whole_tile(masked):
        carry = (acc_s[...], acc_c[...], acc_i[...])
        carry = jax.lax.fori_loop(
            0, tile // chunk, functools.partial(fold, masked=masked), carry)
        acc_s[...], acc_c[...], acc_i[...] = carry

    # only a tile the rows end in pays for the mask
    inside = first + np.int32(tile) <= nvalid

    @pl.when(inside)
    def _():
        whole_tile(False)

    @pl.when(~inside)
    def _():
        whole_tile(True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        sums_ref[...] = acc_s[...]
        counts_ref[...] = acc_c[...]
        inertia_ref[...] = acc_i[...]


def _pass_pallas(xt, centers, nvalid, *, interpret, labels=False, chunk=None, chunks=_CHUNKS):
    f, n = xt.shape
    k = centers.shape[0]
    kp = pl.cdiv(k, 16) * 16  # whole bfloat16 sublane tiles of clusters
    if chunk is None:
        chunk = max(LANE, _CHUNK_BYTES // (f * xt.dtype.itemsize) // LANE * LANE)
    chunk = min(chunk, pl.cdiv(n, LANE) * LANE)
    tile = min(chunks * chunk, pl.cdiv(n, chunk) * chunk)
    c32 = centers.astype(jnp.float32)
    cb = jnp.pad(centers.astype(jnp.bfloat16), ((0, kp - k), (0, 0)))
    # a padding cluster lies infinitely far from every row
    cn = jnp.pad(jnp.sum(c32 * c32, axis=1, keepdims=True), ((0, kp - k), (0, 0)),
                 constant_values=_INF)
    whole = lambda shape: pl.BlockSpec(shape, lambda i, nv: (0, 0))
    # the rows' numbers run along the lanes as the rows do: a tile a grid step
    numbers_spec = [pl.BlockSpec((1, tile), lambda i, nv: (0, i))] if labels else []
    numbers_shape = [jax.ShapeDtypeStruct((1, n), jnp.int32)] if labels else []
    sums, counts, inertia, *numbers = pl.pallas_call(
        functools.partial(_pass_kernel, tile=tile, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(n, tile),),
            in_specs=[
                pl.BlockSpec((f, tile), lambda i, nv: (0, i)),
                whole((kp, f)),
                whole((kp, 1)),
            ],
            out_specs=[whole((kp, f)), whole((kp, LANE)), whole((1, LANE))] + numbers_spec,
            scratch_shapes=[
                pltpu.VMEM((kp, f), jnp.float32),
                pltpu.VMEM((kp, LANE), jnp.float32),
                pltpu.VMEM((1, LANE), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((kp, f), jnp.float32),
            jax.ShapeDtypeStruct((kp, LANE), jnp.float32),
            jax.ShapeDtypeStruct((1, LANE), jnp.float32),
        ] + numbers_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * n * f * kp + 2 * n * f,
            bytes_accessed=n * f * xt.dtype.itemsize + (4 * n if labels else 0),
            transcendentals=0,
        ),
        interpret=interpret,
        name="ht_lloyd_pass",
    )(jnp.asarray(nvalid, jnp.int32).reshape(1), xt, cb, cn)
    three = sums[:k], jnp.sum(counts[:k], axis=1), jnp.sum(inertia)
    return three + (numbers[0].reshape(n),) if labels else three


def _pass_jnp(xt, centers, nvalid, labels=False):
    """The kernel's arithmetic in ``jax.numpy``, whole arrays at a time."""
    f, n = xt.shape
    k = centers.shape[0]
    ok = jnp.arange(n) < nvalid
    x = jnp.where(ok[None, :], xt, jnp.zeros_like(xt))
    xb = x.astype(jnp.bfloat16)
    c32 = centers.astype(jnp.float32)
    cross = jax.lax.dot_general(
        centers.astype(jnp.bfloat16), xb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m2 = jnp.sum(c32 * c32, axis=1, keepdims=True) - _TWO * cross
    number = jnp.argmin(m2, axis=0)
    hit = (number[None, :] == jnp.arange(k)[:, None]) & ok[None, :]
    sums = jax.lax.dot_general(
        hit.astype(jnp.bfloat16), xb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    xf = x.astype(jnp.float32)
    dist = jnp.maximum(jnp.sum(xf * xf, axis=0) + jnp.min(m2, axis=0), _ZERO)
    three = sums, jnp.sum(hit, axis=1, dtype=jnp.float32), jnp.sum(jnp.where(ok, dist, _ZERO))
    return three + (number.astype(jnp.int32),) if labels else three


@jax.named_scope("ht.kmeans.pass")
def lloyd_pass(xt: jax.Array, centers: jax.Array, nvalid, labels: bool = False):
    """Sums ``(k, f)``, counts ``(k,)`` and inertia, float32, of the rows
    ``xt[:, :nvalid]`` (``xt`` is ``(f, n)``, the rows transposed) assigned
    to the nearest of ``centers`` ``(k, f)``.  With ``labels`` (static) a
    fourth result, the number of each row's nearest centre, int32 ``(n,)``:
    ties go to the lowest number, a row whose scores are all NaN gets 0, and
    what rows at or past ``nvalid`` get is not defined.  Without it the pass
    writes nothing of size ``n``."""
    how = _mode()
    if how == "off":
        return _pass_jnp(xt, centers, nvalid, labels)
    return _pass_pallas(xt, centers, nvalid, interpret=(how == "interpret"), labels=labels)
