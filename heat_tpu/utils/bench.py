"""Chain-delta timing, shared by the benchmark suites.

Every derived rate in `benchmarks/cb` and `benchmarks/scaling` is a
chain-delta SLOPE, not a single timed call: time k1 units, time k2
units, divide the difference — any fixed cost (a drain readback,
dispatch overhead, an estimator's n_iter/inertia readbacks) appears in
both timings and cancels.  k2 is found adaptively by doubling the chain
until the delta dwarfs the noise floor.  bench.py pioneered the recipe;
this is the one shared implementation.

Deliberately jax-free at import time: the scaling harness imports it
in subprocesses whose device count is pinned by env before jax loads.
"""

from __future__ import annotations

import time
import typing

__all__ = ["Slope", "chain_slope"]


class Slope(typing.NamedTuple):
    per_unit_s: float
    k1: int
    k2: int
    trials: int
    capped: bool  # doubling hit max_k before the delta reached min_delta

    def fields(self):
        """Self-describing record fields for monitor.record."""
        d = {"method": "chain-delta", "k1": self.k1, "k2": self.k2,
             "trials": self.trials}
        if self.capped:
            # the adaptive guarantee did NOT hold: the measured delta is
            # inside the noise floor — flag it so nobody reads the
            # number as authoritative
            d["delta_below_min"] = True
        return d


def chain_slope(
    run_k, k1: int = 1, min_delta: float = 0.25, trials: int = 3,
    max_k: int = 1025,
) -> Slope:
    """Median per-unit seconds via chain deltas.

    ``run_k(k)`` must execute ``k`` units of identical work and end with
    a readback that forces the computation.  The caller must have
    warmed/compiled ``run_k`` beforehand, and ``run_k`` must not
    recompile as ``k`` varies (python-loop chains and traced trip counts
    are both fine).
    """

    def timed(k):
        t0 = time.perf_counter()
        run_k(k)
        return time.perf_counter() - t0

    t1 = timed(k1)
    # for expensive units the fixed floor is not enough: a 100 ms step
    # only 4x-covers a 0.4 s floor, leaving ~25% jitter in the slope.
    # Scale the target with the (overhead-inflated, so conservative)
    # first probe, capped so one trial stays bounded.
    target = max(min_delta, min(4.0 * t1, 8.0))
    dk = 1
    while True:
        t2 = timed(k1 + dk)
        if t2 - t1 >= target or k1 + dk >= max_k:
            break
        dk *= 2
    k2 = k1 + dk
    slopes = [(t2 - t1) / dk]
    for _ in range(trials - 1):
        a, b = timed(k1), timed(k2)
        slopes.append((b - a) / dk)
    slopes.sort()
    return Slope(
        slopes[len(slopes) // 2], k1, k2, trials, t2 - t1 < target
    )
