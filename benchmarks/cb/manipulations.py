# Continuous-benchmark manipulation workloads (reference: benchmarks/cb/
# manipulations.py: reshape with new_split; plus the concatenate/resplit
# cases from the CI suite, SURVEY.md §6).
#
# Each workload repeats k rounds of identical work ending in one drain, and
# records the chain-delta slope — seconds per ROUND — so the fixed
# readback cost cancels (round 2 recorded 1.86 s for three small
# reshapes; that was the readback, not the reshapes).

import heat_tpu as ht
from heat_tpu.utils.monitor import record

import config


def _reshape_chain(sizes):
    # inputs are created ONCE: creating the arrays inside the chain made
    # round 2's number a measurement of array construction (a host
    # buffer upload), not of reshape
    srcs = [ht.random.random((1000, size), split=1) for size in sizes]

    def run_k(k):
        outs = []
        for _ in range(k):
            outs = [
                ht.reshape(st, (st.size // 10, -1), new_split=1).larray
                for st in srcs
            ]
        config.drain_all(*outs)
    return run_k


def _reshape_lane_chain(sizes):
    # lane-aligned outputs: (1024, s) -> (8s, 128); the 128-wide trailing
    # dim fills TPU tiles exactly, so logical bytes == physical bytes
    srcs = [ht.random.random((1024, size), split=1) for size in sizes]

    def run_k(k):
        outs = []
        for _ in range(k):
            outs = [
                ht.reshape(st, (st.size // 128, 128), new_split=1).larray
                for st in srcs
            ]
        config.drain_all(*outs)
    return run_k


def _concat_chain(a, b):
    def run_k(k):
        out = None
        for _ in range(k):
            out = ht.concatenate([a, b], axis=0).larray
        config.drain(out)
    return run_k


def _resplit_chain(a):
    def run_k(k):
        out = None
        for _ in range(k):
            out = ht.resplit(a, 1).larray
        config.drain(out)
    return run_k


def run():
    run_k = _reshape_chain(config.RESHAPE_SIZES)
    run_k(1)  # warmup: compile
    sl = config.slope(run_k)
    record(
        "reshape", sl.per_unit_s, per=f"{len(config.RESHAPE_SIZES)}-reshapes",
        **sl.fields(),
        # pure data movement: each reshape reads + writes its array once
        **config.hbm_fields(
            sum(2.0 * 1000 * s * 4.0 for s in config.RESHAPE_SIZES),
            sl.per_unit_s,
        ),
        note="the reference-parity (n, 10) output pads its 10-wide lane "
             "dim to 128 in TPU tiles: physical write traffic is ~12.8x "
             "the logical bytes this roofline counts, putting the "
             "physical-traffic fraction near 0.3 — a property of the "
             "shape, not the op; reshape_lane128 scores the op itself",
    )

    # the same op on a lane-aligned (n, 128) output — no tile padding, so
    # the logical-byte roofline is the honest score for the engine
    run_k = _reshape_lane_chain(config.RESHAPE_SIZES)
    run_k(1)
    sl = config.slope(run_k)
    record(
        "reshape_lane128", sl.per_unit_s,
        per=f"{len(config.RESHAPE_SIZES)}-reshapes",
        **sl.fields(),
        **config.hbm_fields(
            sum(2.0 * 1024 * s * 4.0 for s in config.RESHAPE_SIZES),
            sl.per_unit_s,
        ),
    )

    a = ht.random.random((config.CONCAT_N, 64), split=0)
    b = ht.random.random((config.CONCAT_N, 64), split=0)
    run_k = _concat_chain(a, b)
    run_k(1)
    sl = config.slope(run_k)
    record(
        "concatenate", sl.per_unit_s, per="concatenate",
        **sl.fields(),
        # read both inputs, write the joined output: 2x the data volume
        **config.hbm_fields(
            2.0 * 2 * config.CONCAT_N * 64 * 4.0, sl.per_unit_s
        ),
    )

    # resplit on a 1-chip mesh is a metadata relabel (the GSPMD shardings
    # for split 0/1/None coincide), so one unit is ~µs of dispatch — the
    # round-3 row capped out at 1025 links inside the noise floor
    # (delta_below_min).  Raising the chain cap makes the delta resolve:
    # the per-unit number honestly measures the relabel dispatch cost,
    # which IS resplit's cost at comm.size == 1.
    run_k = _resplit_chain(a)
    run_k(1)
    sl = config.slope(run_k, max_k=262_145)
    record(
        "resplit", sl.per_unit_s, per="resplit",
        **sl.fields(),
        note="metadata relabel at comm.size==1 (the 1-chip shardings "
             "coincide): a dispatch-cost row — no traffic or FLOP model "
             "applies; the multi-chip wire structure is asserted in "
             "SCALING_r05 (resplit_0to1: one all-to-all of the local slab)",
    )

    # at-scale variant: on a real mesh resplit moves the whole slab through
    # the tiled transport engine (parallel/transport.py) — one bounded
    # all_to_all per column tile, wire volume exactly one slab per device
    S = a.comm.size
    if S > 1:
        big = ht.random.random((config.RESPLIT_N, 128), split=0)
        run_k = _resplit_chain(big)
        run_k(1)
        sl = config.slope(run_k)
        record(
            "resplit_at_scale", sl.per_unit_s, per="resplit",
            mesh=S, **sl.fields(),
            # each device reads and writes its 1/S slab once; the wire
            # carries the same bytes (SCALING r06 tiled_resplit laws)
            **config.hbm_fields(
                2.0 * config.RESPLIT_N * 128 * 4.0 / S, sl.per_unit_s
            ),
        )


if __name__ == "__main__":
    run()
