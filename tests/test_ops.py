"""Pallas kernel layer tests (heat_tpu/ops).

Kernel logic runs through the Pallas interpreter on the CPU mesh
(HEAT_TPU_PALLAS=interpret) and is compared against dense references —
the reference repo's "no mocks" rule (SURVEY.md §4) applied to kernels.
"""

import os

import numpy as np

import heat_tpu as ht
from .base import TestCase


class _InterpretMode:
    def __enter__(self):
        self._old = os.environ.get("HEAT_TPU_PALLAS")
        os.environ["HEAT_TPU_PALLAS"] = "interpret"

    def __exit__(self, *exc):
        if self._old is None:
            os.environ.pop("HEAT_TPU_PALLAS", None)
        else:
            os.environ["HEAT_TPU_PALLAS"] = self._old


class TestFusedCdist(TestCase):
    def test_matches_dense_reference(self):
        import jax.numpy as jnp
        from heat_tpu.ops import fused_cdist

        rng = np.random.default_rng(1)
        x = rng.standard_normal((19, 7)).astype(np.float32)
        y = rng.standard_normal((11, 7)).astype(np.float32)
        ref = np.sqrt(np.maximum(((x[:, None, :] - y[None, :, :]) ** 2).sum(-1), 0))
        with _InterpretMode():
            out = np.asarray(fused_cdist(jnp.array(x), jnp.array(y)))
        np.testing.assert_allclose(out, ref, atol=1e-4)

    def test_squared_option(self):
        import jax.numpy as jnp
        from heat_tpu.ops import fused_cdist

        rng = np.random.default_rng(2)
        x = rng.standard_normal((8, 5)).astype(np.float32)
        with _InterpretMode():
            d2 = np.asarray(fused_cdist(jnp.array(x), jnp.array(x), sqrt=False))
        ref = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
        np.testing.assert_allclose(d2, ref, atol=1e-4)

    def test_spatial_cdist_fast_path_dispatch(self):
        """spatial.cdist must agree between GSPMD and kernel fast paths."""
        rng = np.random.default_rng(3)
        X = rng.standard_normal((40, 6)).astype(np.float32)
        Y = rng.standard_normal((5, 6)).astype(np.float32)
        a = ht.array(X, split=0)
        b = ht.array(Y)
        base = ht.spatial.cdist(a, b).numpy()
        with _InterpretMode():
            fast = ht.spatial.cdist(a, b)
        self.assertEqual(fast.split, 0)
        np.testing.assert_allclose(fast.numpy(), base, atol=1e-4)

    def test_mixed_dtype_never_downcasts_f32_operand(self):
        """A big bf16 operand paired with a small f32 one must keep the
        f32 side's precision in the cross term (a downcast-to-bf16 path
        fails the tight tolerance below)."""
        import jax.numpy as jnp
        from heat_tpu.ops.cdist import cdist as _cdist

        rng = np.random.default_rng(5)
        # x: integers — exactly representable in bf16, so the reference
        # distance is exact; y: fine-grained f32 values whose mantissa a
        # bf16 downcast would destroy.
        x = rng.integers(-8, 8, (64, 8)).astype(np.float32)
        y = (rng.standard_normal((4, 8)) * (1 + 1e-3)).astype(np.float32)
        big = jnp.asarray(x).astype(jnp.bfloat16)
        d_mixed = np.asarray(_cdist(big, jnp.asarray(y)))
        ref = np.sqrt(
            np.maximum(((x[:, None, :] - y[None, :, :]) ** 2).sum(-1), 0)
        )
        np.testing.assert_allclose(d_mixed, ref, atol=2e-5)
        # sanity: the downcast path really is distinguishable
        d_down = np.asarray(_cdist(big, jnp.asarray(y).astype(jnp.bfloat16)))
        self.assertGreater(np.abs(d_down - ref).max(), 1e-3)

    def test_float64_falls_back_to_gspmd(self):
        """Dtype-authoritative fallback: f64 input must not silently degrade."""
        rng = np.random.default_rng(4)
        X = rng.standard_normal((12, 3))
        a = ht.array(X, split=0, dtype=ht.float64)
        b = ht.array(rng.standard_normal((4, 3)), dtype=ht.float64)
        with _InterpretMode():
            d = ht.spatial.cdist(a, b)
        self.assertEqual(d.dtype, ht.float64)


class TestFlashAttention(TestCase):
    @staticmethod
    def _ref_attn(q, k, v, causal):
        s = np.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            m = np.tril(np.ones(s.shape[-2:], bool))
            s = np.where(m, s, -1e30)
        e = np.exp(s - s.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        return np.einsum("bqk,bkd->bqd", p, v)

    def test_matches_reference(self):
        import jax.numpy as jnp
        from heat_tpu.ops import flash_attention

        rng = np.random.default_rng(5)
        q = rng.standard_normal((3, 40, 16)).astype(np.float32)
        for causal in (False, True):
            with _InterpretMode():
                out = np.asarray(
                    flash_attention(jnp.array(q), jnp.array(q), jnp.array(q), causal=causal)
                )
            np.testing.assert_allclose(out, self._ref_attn(q, q, q, causal), atol=1e-4)

    def test_four_dim_layout_and_grad(self):
        import jax, jax.numpy as jnp
        from heat_tpu.ops import flash_attention

        rng = np.random.default_rng(6)
        q = jnp.array(rng.standard_normal((2, 4, 24, 8)).astype(np.float32))
        with _InterpretMode():
            out = flash_attention(q, q, q, causal=True)
            self.assertEqual(out.shape, q.shape)
            g = jax.grad(lambda x: flash_attention(x, x, x, causal=True).sum())(q)
        self.assertTrue(bool(jnp.isfinite(g).all()))

    def test_cross_attention_uneven_kv(self):
        import jax.numpy as jnp
        from heat_tpu.ops import flash_attention

        rng = np.random.default_rng(7)
        q = rng.standard_normal((2, 13, 8)).astype(np.float32)
        kv = rng.standard_normal((2, 29, 8)).astype(np.float32)
        with _InterpretMode():
            out = np.asarray(
                flash_attention(jnp.array(q), jnp.array(kv), jnp.array(kv))
            )
        np.testing.assert_allclose(out, self._ref_attn(q, kv, kv, False), atol=1e-4)


class TestHaloExchange(TestCase):
    def test_three_point_stencil_matches_dense(self):
        from heat_tpu.ops import map_with_halos

        xs = np.arange(24, dtype=np.float32)
        expect = np.pad(xs, 1)[:-2] + xs + np.pad(xs, 1)[2:]
        for split in (0, None):
            x = ht.array(xs, split=split)
            out = map_with_halos(lambda w, e: w[:-2] + w[1:-1] + w[2:], x, 1)
            self.assertEqual(out.split, split)
            np.testing.assert_allclose(out.numpy(), expect)

    def test_uneven_split_no_pad_leak(self):
        from heat_tpu.ops import map_with_halos

        xs = np.arange(13, dtype=np.float32)  # 13 over 8 devices: pad-heavy
        x = ht.array(xs, split=0)
        out = map_with_halos(lambda w, e: w[:-2] + w[1:-1] + w[2:], x, 1)
        expect = np.pad(xs, 1)[:-2] + xs + np.pad(xs, 1)[2:]
        np.testing.assert_allclose(out.numpy(), expect)

    def test_wrap_mode_periodic(self):
        from heat_tpu.ops import map_with_halos

        xs = np.arange(16, dtype=np.float32)
        x = ht.array(xs, split=0)
        out = map_with_halos(
            lambda w, e: w[:-2] + w[1:-1] + w[2:], x, 1, wrap=True
        )
        expect = np.roll(xs, 1) + xs + np.roll(xs, -1)
        np.testing.assert_allclose(out.numpy(), expect)

    def test_2d_stencil_on_split_rows(self):
        from heat_tpu.ops import map_with_halos

        rng = np.random.default_rng(8)
        img = rng.standard_normal((24, 5)).astype(np.float32)
        x = ht.array(img, split=0)
        out = map_with_halos(lambda w, e: w[2:] - w[:-2], x, 1)
        expect = np.pad(img, ((1, 1), (0, 0)))[2:] - np.pad(img, ((1, 1), (0, 0)))[:-2]
        np.testing.assert_allclose(out.numpy(), expect, atol=1e-6)
