# Continuous-benchmark quantized-collective rows (round 17, ISSUE 16):
# the absmax int8 wire format driven through the REAL movement engines —
# the tiled resplit's all_to_all and the ring matmul's ppermute chain —
# with the forced arm (wire.set_mode) so the rows are deterministic on
# any mesh, plus a cold tuned explore afterwards so each row records the
# arm the tuning table actually resolves to on this machine.
#
# Honesty contract: on the CPU CI mesh the quantized arm usually does
# NOT win on wall (no ICI to relieve; the quant/dequant pass is extra
# work), so the wall columns carry wide cited tolerances (history.py)
# and the headline is the ON-WIRE byte delta — taken from the wire
# ledger's exact per-dispatch accounting (wire.stats bytes_logical vs
# bytes_wire, the same numbers the heat_tpu_wire_* gauges export), not
# re-modeled here — alongside the measured max elementwise error vs the
# f32-wire run of the same program (the absmax/254-per-scale-row bound
# the docs cite).
import numpy as np

import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import autotune, telemetry, wire
from heat_tpu.core.dndarray import _to_physical
from heat_tpu.parallel import overlap, transport
from heat_tpu.utils.monitor import record

import config


class _Forced:
    """Scoped forced wire arm: counters cleared on entry so the byte
    columns are exactly this workload's dispatches."""

    def __init__(self, mode):
        self.mode = mode

    def __enter__(self):
        self.prev = wire.set_mode(self.mode)
        telemetry.reset_group("wire")
        return self

    def __exit__(self, *exc):
        wire.set_mode(self.prev)
        telemetry.reset_group("wire")
        return False


def _wire_fields(stats, ref, out):
    """The headline columns: exact ledger bytes + measured error."""
    logical = int(stats["bytes_logical"])
    wired = int(stats["bytes_wire"])
    return {
        "wire_bytes_logical": logical,
        "wire_bytes_on_wire": wired,
        "wire_bytes_saved": logical - wired,
        "wire_ratio": round(logical / max(wired, 1), 2),
        "quantized_dispatches": int(stats["quantized_dispatches"]),
        "max_elem_error": float(np.abs(out - ref).max()),
    }


def _tuned_arm_note(run):
    """Run one cold explore under the tuning plane (wire mode ``on``)
    and report the arm the table resolves for this site — the measured
    choice a real deployment would stick with."""
    prev_mode = wire.set_mode("on")
    prev_on = autotune.set_enabled(True)
    autotune.reset()
    try:
        for _ in range(autotune.explore_k()):
            run()
        rows = [
            r for r in autotune.report()["rows"]
            if tuple(r.get("arms", ())) == wire.WIRE_ARMS
        ]
        winners = [r["winner"] or "exploring" for r in rows]
        arm = winners[0] if winners else "wire_f32"
        return arm, f" measured arm choice after a cold explore: {arm}"
    finally:
        autotune.set_enabled(prev_on)
        autotune.reset()
        wire.set_mode(prev_mode)


def _resplit_wire(rng):
    shape = config.WIRE_RESPLIT_SHAPE
    x = rng.standard_normal(shape).astype(np.float32)
    comm = ht.parallel.get_comm()

    def run_once():
        phys = _to_physical(jnp.asarray(x), shape, 0, comm)
        return transport.tiled_resplit(phys, shape, 0, 1, comm)

    with _Forced("off"):
        ref = np.asarray(run_once())
    with _Forced("int8"):
        run_once()  # warmup: compile the quantized program
        telemetry.reset_group("wire")
        out = run_once()

        def run_k(reps):
            y = None
            for _ in range(reps):
                y = run_once()
            config.drain(y)

        sl = config.slope(run_k)
        st = wire.stats()
        out = np.asarray(out)
    arm, note_arm = _tuned_arm_note(run_once)
    record(
        "resplit_wire_int8", sl.per_unit_s, per="resplit",
        rows=shape[0], cols=shape[1], forced_arm="wire_int8", arm=arm,
        **sl.fields(), **_wire_fields(st, ref[: shape[0], : shape[1]],
                                      out[: shape[0], : shape[1]]),
        note="split 0->1 all_to_all with int8 tiles + f32 scales on the "
             "wire, dequant on landing; the byte columns are the wire "
             "ledger's exact per-dispatch accounting (>=3x is the "
             "acceptance bar), max_elem_error is measured against the "
             "f32-wire run and bounded by absmax/254 per scale row.  "
             "Wall rides the forced int8 arm; on CPU the quant pass is "
             "extra work, hence the wide cited tolerance." + note_arm,
    )


def _matmul_ring_wire(rng):
    m, k, n = config.WIRE_MM_M, config.WIRE_MM_K, config.WIRE_MM_N
    A = rng.standard_normal((m, k)).astype(np.float32)
    B = rng.standard_normal((k, n)).astype(np.float32)

    def run_once():
        from heat_tpu.core import fusion

        a = ht.array(A, split=0)
        b = ht.array(B, split=0)
        overlap.set_mode("ring")
        try:
            with fusion.fuse(False):
                return np.asarray(ht.matmul(a, b).larray)
        finally:
            overlap.set_mode(None)

    with _Forced("off"):
        ref = run_once()
    with _Forced("int8"):
        run_once()  # warmup: compile the quantized ring
        telemetry.reset_group("wire")
        out = run_once()

        def run_k(reps):
            y = None
            for _ in range(reps):
                y = run_once()
            config.drain(jnp.asarray(y))

        sl = config.slope(run_k)
        st = wire.stats()
        sched = (overlap.stats()["last"] or {}).get("schedule", "?")
    arm, note_arm = _tuned_arm_note(run_once)
    record(
        "matmul_ring_wire", sl.per_unit_s, per="matmul",
        m=m, k=k, n=n, schedule=sched, forced_arm="wire_int8", arm=arm,
        **sl.fields(), **_wire_fields(st, ref, out),
        **config.mfu_fields(
            config.matmul_flops_mkn(m, k, n), sl.per_unit_s,
            config.PEAK_BF16_TFLOPS, "bf16",
        ),
        note="ring matmul with int8 moving blocks (one f32 scale per "
             "k-slice) hopping the ppermute chain beside their scale "
             "table, f32 accumulation at the units; byte columns are "
             "the exact wire-ledger accounting over the (S-1) hops.  "
             "The error column is a dot-product of ~k quantized terms, "
             "well under 1% of the output magnitude for unit-normal "
             "operands." + note_arm,
    )


def run():
    rng = np.random.default_rng(17)
    _resplit_wire(rng)
    _matmul_ring_wire(rng)


if __name__ == "__main__":
    run()
