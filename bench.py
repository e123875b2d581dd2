"""Benchmark harness: distributed matmul TFLOP/s per chip.

The first north-star metric from BASELINE.md ("distributed matmul
TFLOP/s/chip ... ≥40% MFU"). Runs ht.matmul on bfloat16 split DNDarrays —
the framework's own GSPMD matmul path — and reports achieved TFLOP/s per
chip. ``vs_baseline`` is the achieved fraction of the 40%-MFU target
(value / (0.40 * peak)); > 1.0 beats the target.

A device measurement: with no TPU, or a ``device_kind`` missing from the
peak table (``heat_tpu/core/roofline.py``), it raises instead of
shrinking the problem or assuming a peak.

Prints exactly ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": ...}
"""

import json
import time

N = 8192


def peak_tflops_bf16(device) -> float:
    """Per-chip bf16 peak of ``device`` from the one peak table; raises
    for a non-TPU device or an unknown ``device_kind``."""
    from heat_tpu.core import roofline

    return roofline.require_peaks(device)["bf16_tflops"]


def main() -> None:
    import jax

    import heat_tpu as ht
    from heat_tpu.utils import compile_cache

    dev = jax.devices()[0]
    n_chips = len(jax.devices())
    peak = peak_tflops_bf16(dev)
    compile_cache.enable()

    a = ht.random.randn(N, N, dtype=ht.bfloat16, split=0)
    # b ~ N(0, 1/N) keeps the chained products finite: an overflowing
    # chain trips the non-finite guard, whose eager replay would be timed
    b = ht.random.randn(N, N, dtype=ht.bfloat16, split=None) * (1.0 / N ** 0.5)
    b.larray.block_until_ready()

    def chain(k: int) -> float:
        """k chained ht.matmuls ended by a scalar readback; timing uses
        the slope between two chain lengths so the fixed readback and
        dispatch cost cancels."""
        c = a
        t0 = time.perf_counter()
        for _ in range(k):
            c = ht.matmul(c, b)
        float(ht.sum(c.astype(ht.float32) * 0.0))
        return time.perf_counter() - t0

    # 100 extra matmuls ≈ 560 ms at peak.  Use the median slope of three
    # trials — a min() would crown one lucky jitter sample with >peak FLOP/s.
    k1, k2 = 8, 108
    for k in (k1, k2):  # warmup: each chain length is its own fused program
        chain(k)
    slopes = []
    for _ in range(3):
        t1, t2 = chain(k1), chain(k2)
        slopes.append((t2 - t1) / (k2 - k1))
    best = sorted(slopes)[len(slopes) // 2]

    tflops_per_chip = 2.0 * N * N * N / best / n_chips / 1e12
    result = {
        "metric": "distributed_matmul_tflops_per_chip",
        "value": round(tflops_per_chip, 2),
        "unit": "TFLOP/s/chip (bf16, n=%d, %d chip(s), %s)" % (N, n_chips, dev.device_kind),
        "vs_baseline": round(tflops_per_chip / (0.40 * peak), 3),
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": n_chips,
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
