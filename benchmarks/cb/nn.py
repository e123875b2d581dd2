# Continuous-benchmark NN-kernel workloads (no reference counterpart — the
# reference's cb suite has no attention or MoE; these cover the kernels this
# framework adds: flash attention and the expert-parallel MoE FFN).
#
# Attention and MoE chain k dependent passes inside ONE jitted fori_loop
# whose trip count is a traced argument (no recompiles as k varies), so the
# chain-delta slope (config.slope) times the kernel alone — a single
# drain per pass would record the readback as if it were kernel time.
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from heat_tpu.utils.monitor import record

import config


@functools.partial(jax.jit, static_argnames=("causal",))
def _attn_chain(q, n, causal=True):
    from heat_tpu.ops.attention import flash_attention

    return lax.fori_loop(
        0, n, lambda i, v: flash_attention(v, v, v, causal=causal), q
    )


@jax.jit
def _moe_chain(x, gate, w_in, w_out, n):
    from heat_tpu.parallel.expert import moe_ffn

    return lax.fori_loop(
        0, n, lambda i, v: moe_ffn(v, gate, w_in, w_out, k=2)[0], x
    )


def _resnet_bench():
    # the BASELINE.md DP flagship: ResNet-50 train step, batch sharded over
    # the mesh, grad all-reduce implicit in the jitted step.  train_step
    # returns a device scalar (no per-step sync), so a python loop of k
    # steps ending in one drain is a clean chain.
    import optax

    import heat_tpu as ht

    rng = np.random.default_rng(1)
    b, img = config.RESNET_BATCH, config.RESNET_IMG
    dt = jnp.bfloat16 if config.ON_TPU else jnp.float32
    Xh = rng.standard_normal((b, img, img, 3)).astype(np.float32).astype(dt)
    yh = rng.integers(0, 1000, b)
    model = ht.nn.DataParallel(
        ht.models.ResNet50(num_classes=1000, dtype=dt),
        optimizer=ht.optim.DataParallelOptimizer(optax.sgd(0.1)),
    )
    model.init(0, Xh[: min(b, 8)])
    X = ht.array(Xh, split=0)
    y = ht.array(yh, split=0)

    def run_k(k):
        loss = None
        for _ in range(k):
            loss = model.train_step(X, y)
        config.drain(loss)

    run_k(1)  # warmup: compile (incl. drain)
    sl = config.slope(run_k)
    rn_flops = config.resnet50_step_flops(b) if img == 224 else 0
    record(
        "resnet50_dp_step", sl.per_unit_s, per="train-step",
        batch=b, image=img, **sl.fields(),
        **config.mfu_fields(
            rn_flops, sl.per_unit_s, config.PEAK_BF16_TFLOPS, "bf16"
        ),
    )
    del model, X

    # space-to-depth stem variant (round 3): the 7x7/s2 3-channel stem
    # becomes a 4x4/s1 conv over 12 channels in block space — the input
    # transform happens once in the pipeline (models/resnet.py)
    from heat_tpu.models.resnet import space_to_depth

    Xs = np.asarray(space_to_depth(jnp.asarray(Xh)))
    model2 = ht.nn.DataParallel(
        ht.models.ResNet50(num_classes=1000, dtype=dt, s2d_stem=True),
        optimizer=ht.optim.DataParallelOptimizer(optax.sgd(0.1)),
    )
    model2.init(0, Xs[: min(b, 8)])
    X2 = ht.array(Xs, split=0)

    def run_k2(k):
        loss = None
        for _ in range(k):
            loss = model2.train_step(X2, y)
        config.drain(loss)

    run_k2(1)
    sl = config.slope(run_k2)
    record(
        "resnet50_s2d_dp_step", sl.per_unit_s, per="train-step",
        batch=b, image=img, stem="space-to-depth", **sl.fields(),
        **config.mfu_fields(
            rn_flops, sl.per_unit_s, config.PEAK_BF16_TFLOPS, "bf16"
        ),
        **({"note": "same-FLOP model as resnet50_dp_step (the s2d stem "
                    "re-expresses the 7x7/s2 conv, ~same useful work)"}
           if config.ON_TPU else {}),
    )


def run():
    rng = np.random.default_rng(0)
    dt = jnp.bfloat16 if config.ON_TPU else jnp.float32

    bh, s_, d = config.ATTN_BH, config.ATTN_S, config.ATTN_D
    q = jnp.asarray(rng.standard_normal((bh, s_, d)), dt)

    for causal, row in ((True, "flash_attention_forward"),
                        (False, "flash_attention_forward_noncausal")):
        def attn_k(k, _c=causal):
            config.drain(_attn_chain(q, jnp.int32(k), causal=_c))

        attn_k(1)  # warmup: compile once (trip count is traced)
        sl = config.slope(attn_k)
        record(
            row, sl.per_unit_s, per="attention-pass",
            causal=causal, bh=bh, s=s_, d=d, **sl.fields(),
            flop_model="4*bh*s^2*d" + (", causal/2" if causal else ""),
            **config.mfu_fields(
                config.attention_flops(bh, s_, d, causal=causal),
                sl.per_unit_s, config.PEAK_BF16_TFLOPS, "bf16",
            ),
        )
    del q

    t, dm, h = config.MOE_T, config.MOE_D, config.MOE_H
    x = jnp.asarray(rng.standard_normal((t, dm)), dt)
    gate = jnp.asarray(rng.standard_normal((dm, 8)), dt)
    w_in = jnp.asarray(rng.standard_normal((8, dm, h)) / 32, dt)
    w_out = jnp.asarray(rng.standard_normal((8, h, dm)) / 32, dt)

    def moe_k(k):
        config.drain(_moe_chain(x, gate, w_in, w_out, jnp.int32(k)))

    moe_k(1)
    sl = config.slope(moe_k)
    # the useful-MFU gap vs hardware utilization is capacity headroom:
    # with capacity_factor=2.0 half the expert slots compute dead work by
    # design, so the GEMMs run ~2x the routed FLOPs
    from heat_tpu.parallel.expert import expert_capacity

    cap = expert_capacity(t, 8, 2, 2.0)
    hw_flops = config.moe_flops(8 * cap, dm, h, k=1)  # every slot, incl. dead
    hw = config.mfu_fields(
        hw_flops, sl.per_unit_s, config.PEAK_BF16_TFLOPS, "bf16"
    )
    record(
        "moe_ffn_forward", sl.per_unit_s, per="moe-pass",
        tokens=t, d_model=dm, d_ff=h, k=2, capacity_factor=2.0,
        **sl.fields(),
        flop_model="tokens*k*(2*d*h + 2*h*d); routed-token model, "
                   "capacity drops not credited",
        **config.mfu_fields(
            config.moe_flops(t, dm, h, k=2), sl.per_unit_s,
            config.PEAK_BF16_TFLOPS, "bf16",
        ),
        **({"hardware_tflops": hw["useful_tflops"],
            "hardware_mfu": hw["mfu"],
            "hardware_note": "incl. capacity-slot dead work (cf=2.0 -> "
                             "2x routed FLOPs); the kernel itself runs at "
                             "hardware_mfu"} if hw else {}),
    )
    del x, gate, w_in, w_out

    _resnet_bench()


if __name__ == "__main__":
    run()
