"""Regression tests for the round-3 advisor findings:
gru_unit packed weight layout, interpolate align_mode=1, shuffle_batch
seed=0 freshness, max_unpool2d duplicate-index determinism, fluid
spectral_norm power-iteration state persistence."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


def _np(t):
    return np.asarray(t.numpy() if hasattr(t, "numpy") else t)


class TestGruUnitWeightLayout:
    def test_packed_blocks_match_reference_gemms(self):
        """The reference kernel (gru_unit_op.h) reads the [D,3D] buffer as
        a packed [D,2D] block then a [D,D] block (GEMM ldb=2D then ldb=D),
        NOT as column slices."""
        from paddle_tpu.nn.rnn import gru_unit
        r = np.random.RandomState(0)
        b, d = 3, 4
        x_gates = r.randn(b, 3 * d).astype("f4")
        hprev = r.randn(b, d).astype("f4")
        weight = r.randn(d, 3 * d).astype("f4")
        bias = r.randn(1, 3 * d).astype("f4")

        # numpy model of the reference kernel's memory access
        wf = weight.reshape(-1)
        w_rz = wf[:2 * d * d].reshape(d, 2 * d)
        w_c = wf[2 * d * d:].reshape(d, d)
        g = x_gates + bias
        rz = g[:, :2 * d] + hprev @ w_rz
        sig = lambda a: 1.0 / (1.0 + np.exp(-a))
        u = sig(rz[:, :d])
        rr = sig(rz[:, d:])
        rhp = rr * hprev
        c = np.tanh(g[:, 2 * d:] + rhp @ w_c)
        h_want = (1.0 - u) * hprev + u * c

        gate, rhp_got, h_got = gru_unit(
            paddle.to_tensor(x_gates), paddle.to_tensor(hprev),
            paddle.to_tensor(weight), paddle.to_tensor(bias))
        np.testing.assert_allclose(_np(h_got), h_want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(_np(rhp_got), rhp, rtol=1e-5, atol=1e-5)

    def test_column_split_would_differ(self):
        """Sanity: the two readings genuinely disagree for a generic
        buffer, so the layout test above has teeth."""
        r = np.random.RandomState(1)
        d = 4
        weight = r.randn(d, 3 * d).astype("f4")
        wf = weight.reshape(-1)
        packed_rz = wf[:2 * d * d].reshape(d, 2 * d)
        col_rz = weight[:, :2 * d]
        assert not np.allclose(packed_rz, col_rz)


class TestInterpolateAlignMode:
    def test_align_mode_1_uses_asymmetric_coords(self):
        """align_mode=1 + align_corners=False: src = i * in/out (the fluid
        resize_bilinear default), vs half-pixel for align_mode=0."""
        x = np.arange(8, dtype="f4").reshape(1, 1, 8)
        out = F.interpolate(paddle.to_tensor(x), size=[4], mode="linear",
                            align_corners=False, align_mode=1,
                            data_format="NCW")
        # src coords: i * 8/4 = 0,2,4,6 -> exact gathers, no lerp
        np.testing.assert_allclose(_np(out)[0, 0], [0.0, 2.0, 4.0, 6.0],
                                   rtol=1e-6)

    def test_align_mode_0_half_pixel_differs(self):
        x = np.arange(8, dtype="f4").reshape(1, 1, 8)
        out0 = F.interpolate(paddle.to_tensor(x), size=[4], mode="linear",
                             align_corners=False, align_mode=0,
                             data_format="NCW")
        # half-pixel: src = (i+0.5)*2 - 0.5 = 0.5,2.5,4.5,6.5
        np.testing.assert_allclose(_np(out0)[0, 0], [0.5, 2.5, 4.5, 6.5],
                                   rtol=1e-6)

    def test_fluid_resize_bilinear_default_is_mode_1(self):
        from paddle_tpu.fluid import layers as FL
        x = np.arange(16, dtype="f4").reshape(1, 1, 4, 4)
        # fluid default: align_corners=True ignores align_mode; force
        # the 1.x non-corner path
        out = FL.resize_bilinear(paddle.to_tensor(x), out_shape=[2, 2],
                                 align_corners=False)
        # align_mode=1: src = i*2 -> rows/cols 0,2 exactly
        np.testing.assert_allclose(_np(out)[0, 0],
                                   [[0.0, 2.0], [8.0, 10.0]], rtol=1e-6)


class TestShuffleBatchSeed:
    def test_seed0_fresh_per_call(self):
        from paddle_tpu.ops.legacy import shuffle_batch
        paddle.seed(7)
        x = paddle.to_tensor(np.arange(64, dtype="f4").reshape(64, 1))
        perms = {tuple(_np(shuffle_batch(x)).ravel().tolist())
                 for _ in range(4)}
        assert len(perms) > 1, "seed=0 must not repeat the permutation"

    def test_nonzero_seed_deterministic(self):
        from paddle_tpu.ops.legacy import shuffle_batch
        x = paddle.to_tensor(np.arange(16, dtype="f4").reshape(16, 1))
        a = _np(shuffle_batch(x, seed=3))
        b = _np(shuffle_batch(x, seed=3))
        np.testing.assert_array_equal(a, b)


class TestMaxUnpoolDuplicateIndices:
    def test_duplicate_indices_take_max(self):
        """Overlapping windows can record the same input cell twice; the
        scatter must be order-independent (max), not last-write-wins."""
        from paddle_tpu.vision.ops import _max_unpool2d_raw
        import jax.numpy as jnp
        x = jnp.array([[[[2.0, 5.0]]]])           # [1,1,1,2] pooled vals
        idx = jnp.array([[[[3, 3]]]], dtype=jnp.int32)  # same flat target
        out = np.asarray(_max_unpool2d_raw(x, idx, output_hw=(2, 2)))
        assert out[0, 0, 1, 1] == 5.0
        assert out.sum() == 5.0                    # untouched cells zero

    def test_negative_values_survive_zero_fill(self):
        from paddle_tpu.vision.ops import _max_unpool2d_raw
        import jax.numpy as jnp
        x = jnp.array([[[[-3.0]]]])
        idx = jnp.array([[[[2]]]], dtype=jnp.int32)
        out = np.asarray(_max_unpool2d_raw(x, idx, output_hw=(2, 2)))
        assert out[0, 0, 1, 0] == -3.0


class TestSpectralNormStatePersists:
    def test_uv_advance_across_calls(self):
        """Each fluid.spectral_norm call must resume power iteration from
        the previous call's u/v (ref kernel updates U/V in place)."""
        from paddle_tpu.fluid import layers as FL
        paddle.seed(11)
        r = np.random.RandomState(2)
        w = paddle.to_tensor(r.randn(6, 8).astype("f4"))
        sigma_true = np.linalg.svd(_np(w), compute_uv=False)[0]

        # one power iteration per call, same layer-name via explicit name
        outs = [FL.spectral_norm(w, power_iters=1, name="sn_fix")
                for _ in range(25)]
        # sigma estimate implied by the normalized output converges to the
        # true spectral norm only if u/v persist across calls
        est = _np(w)[0, 0] / _np(outs[-1])[0, 0]
        assert abs(est - sigma_true) / sigma_true < 1e-3, \
            (est, sigma_true)


class TestPoolCeilMode:
    """ceil_mode was silently dropped by the functional pool wrapper
    (found wiring the protobuf pool2d translator)."""

    def test_max_pool_ceil_shape_and_values(self):
        import torch
        import torch.nn.functional as TF
        x = np.random.RandomState(0).randn(1, 2, 6, 6).astype("f4")
        want = TF.max_pool2d(torch.from_numpy(x), 3, stride=2,
                             ceil_mode=True).numpy()
        got = _np(F.max_pool2d(paddle.to_tensor(x), 3, stride=2,
                               ceil_mode=True))
        assert got.shape == want.shape == (1, 2, 3, 3)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_avg_pool_ceil_exclusive(self):
        import torch
        import torch.nn.functional as TF
        x = np.random.RandomState(1).randn(1, 1, 5, 5).astype("f4")
        want = TF.avg_pool2d(torch.from_numpy(x), 2, stride=2,
                             ceil_mode=True,
                             count_include_pad=False).numpy()
        got = _np(F.avg_pool2d(paddle.to_tensor(x), 2, stride=2,
                               ceil_mode=True, count_include_pad=False))
        assert got.shape == want.shape == (1, 1, 3, 3)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_ceil_mode_off_unchanged(self):
        x = np.random.RandomState(2).randn(1, 1, 7, 7).astype("f4")
        got = _np(F.max_pool2d(paddle.to_tensor(x), 3, stride=2))
        assert got.shape == (1, 1, 3, 3)

    def test_ceil_stride_gt_kernel_clamps(self):
        """stride > kernel with ceil_mode: windows starting entirely in
        the high pad are NOT windows (torch clamp rule) — no -inf cells,
        no extra output row."""
        import torch
        import torch.nn.functional as TF
        x = np.random.RandomState(4).randn(1, 1, 4, 4).astype("f4")
        want = TF.max_pool2d(torch.from_numpy(x), 1, stride=2,
                             ceil_mode=True).numpy()
        got = _np(F.max_pool2d(paddle.to_tensor(x), 1, stride=2,
                               ceil_mode=True))
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-6)
        assert np.all(np.isfinite(got))
        # avg exclusive must not produce 0/0 NaN either
        got_a = _np(F.avg_pool2d(paddle.to_tensor(x), 1, stride=2,
                                 ceil_mode=True, count_include_pad=False))
        assert np.all(np.isfinite(got_a))

    def test_pool3d_ceil_mode(self):
        import torch
        import torch.nn.functional as TF
        x = np.random.RandomState(5).randn(1, 1, 5, 5, 5).astype("f4")
        want = TF.max_pool3d(torch.from_numpy(x), 2, stride=2,
                             ceil_mode=True).numpy()
        got = _np(F.max_pool3d(paddle.to_tensor(x), 2, stride=2,
                               ceil_mode=True))
        assert got.shape == want.shape == (1, 1, 3, 3, 3)
        np.testing.assert_allclose(got, want, rtol=1e-6)


class TestModeOp:
    def test_matches_torch(self):
        import torch
        x = np.array([[1, 2, 2, 3, 1, 1], [5, 5, 4, 4, 4, 6]], "i8")
        tv, ti = torch.mode(torch.from_numpy(x), -1)
        v, i = paddle.mode(paddle.to_tensor(x), axis=-1)
        np.testing.assert_array_equal(_np(v), tv.numpy())
        np.testing.assert_array_equal(_np(i), ti.numpy())

    def test_float_and_axis(self):
        import torch
        x = np.random.RandomState(0).randint(0, 4, (3, 5, 4)).astype("f4")
        tv, ti = torch.mode(torch.from_numpy(x), 1)
        v, i = paddle.mode(paddle.to_tensor(x), axis=1)
        np.testing.assert_array_equal(_np(v), tv.numpy())
        np.testing.assert_array_equal(_np(i), ti.numpy())

    def test_keepdim(self):
        x = np.array([[1.0, 1.0, 2.0]], "f4")
        v, i = paddle.mode(paddle.to_tensor(x), axis=-1, keepdim=True)
        assert _np(v).shape == (1, 1) and _np(v)[0, 0] == 1.0
