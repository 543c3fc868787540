"""Pallas kernels vs reference implementations: values and gradients.

Runs in interpret mode on the CPU test platform (tests/conftest.py) — the
same kernel bodies compile via Mosaic on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuic.kernels import (flash_attention, fold_bn, fused_conv_bn_relu,
                           fused_weighted_cross_entropy)
from tpuic.train.loss import weighted_cross_entropy
from _gates import requires_shard_map


def _rand(key, shape):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32)


def _dense_attention(q, k, v):
    """Reference attention the flash kernel must match."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _dense_loss(q, k, v):
    return jnp.sum(_dense_attention(q, k, v) ** 2)


class TestFlashAttention:
    @pytest.mark.parametrize("n", [8, 17, 64])  # 17: padding path
    def test_matches_dense(self, n):
        b, h, d = 2, 4, 16
        q, k, v = (_rand(i, (b, n, h, d)) for i in range(3))
        got = flash_attention(q, k, v, block_q=8, block_k=8)
        want = _dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_gradients_match_dense(self):
        b, n, h, d = 2, 12, 2, 8
        q, k, v = (_rand(i + 10, (b, n, h, d)) for i in range(3))

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=8, block_k=8) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(_dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    @requires_shard_map
    def test_gradients_match_dense_sharded(self, devices8):
        """Backward kernels under shard_map over the data axis."""
        from tpuic.config import MeshConfig
        from tpuic.runtime.mesh import make_mesh

        mesh = make_mesh(MeshConfig(data=8), devices8)
        b, n, h, d = 8, 12, 2, 8
        q, k, v = (_rand(i + 20, (b, n, h, d)) for i in range(3))

        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, 8, 8, None, mesh) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(_dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("n", [40, 150])  # padded 128 / 256, one k pass
    def test_auto_blocks_match_dense(self, n):
        """Default (None) block sizes resolve by sequence length
        (_resolve_blocks) and must stay exact through forward AND backward —
        the lse padding depends on the resolved blocks, so fwd/bwd must
        agree on them."""
        b, h, d = 1, 2, 8
        q, k, v = (_rand(i + 30, (b, n, h, d)) for i in range(3))

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)  # auto blocks

        np.testing.assert_allclose(float(loss_flash(q, k, v)),
                                   float(_dense_loss(q, k, v)), rtol=1e-4)
        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(_dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    def test_backward_residuals_are_linear_in_n(self):
        """The saved residuals must be O(N·D) — (q, k, v, o, lse), never an
        [N, N] probability matrix (the point of the flash backward)."""
        b, n, h, d = 1, 64, 1, 8
        q, k, v = (_rand(i, (b, n, h, d)) for i in range(3))
        _, vjp_fn = jax.vjp(
            lambda a, b_, c: flash_attention(a, b_, c, 8, 8), q, k, v)
        leaves = jax.tree_util.tree_leaves(vjp_fn)
        assert leaves, "no residuals found"
        biggest = max(x.size for x in leaves if hasattr(x, "size"))
        assert biggest <= b * n * h * d, (
            f"residual of {biggest} elements suggests an O(N^2) save")

    @pytest.mark.parametrize("n", [197, 130])  # 197: ViT-B; both pad
    def test_packed_layout_matches_folded_bitwise(self, n):
        """The lane-packed variant (kernel I/O in the model's natural
        [B, N, H*64] layout — no 2x lane-padding expansion, no transpose
        copies; PERF_ANALYSIS.md §10f) must be BITWISE the folded kernel:
        same dots in the same order, only the memory layout differs.
        Covers forward, lse residual, and all three gradients."""
        import importlib
        fa = importlib.import_module("tpuic.kernels.flash_attention")
        b, h, d = 2, 4, 64
        assert fa._use_packed(h, d)
        q, k, v = (_rand(i + 50, (b, n, h, d)) for i in range(3))
        bq, bk = fa._resolve_blocks(n, None, None)
        out_p, lse_p = fa._flash_fwd_packed(q, k, v, bq, bk, True,
                                            with_lse=True)
        out_f, lse_f = fa._flash_fwd(q, k, v, bq, bk, True, with_lse=True)
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_f))
        np.testing.assert_array_equal(np.asarray(lse_p), np.asarray(lse_f))
        g = _rand(99, (b, n, h, d))
        grads_p = fa._flash_bwd_packed(q, k, v, out_p, lse_p, g, bq, bk, True)
        grads_f = fa._flash_bwd(q, k, v, out_f, lse_f, g, bq, bk, True)
        for a, b_ in zip(grads_p, grads_f):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def test_packed_dispatch_gradients_match_dense(self):
        """The public flash_attention dispatches to the packed variant at
        head_dim 64 / even heads; end-to-end custom-vjp gradients must
        match dense (and the non-qualifying vit-tiny-like head_dim 16
        falls back to the folded path — covered by every other test in
        this class)."""
        b, n, h, d = 2, 70, 2, 64
        q, k, v = (_rand(i + 60, (b, n, h, d)) for i in range(3))

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v) ** 2)

        np.testing.assert_allclose(float(loss_flash(q, k, v)),
                                   float(_dense_loss(q, k, v)), rtol=1e-4)
        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(_dense_loss, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    def test_packed_honors_static_valid(self):
        """valid_len (the ulysses caller-side token padding) must mask the
        same keys in the packed variant: attention over the first
        ``valid`` tokens only, identical to dense on the valid slice."""
        b, n, h, d, valid = 1, 64, 2, 64, 50
        q, k, v = (_rand(i + 70, (b, n, h, d)) for i in range(3))
        got = flash_attention(q, k, v, valid_len=valid)
        want = _dense_attention(q[:, :valid], k[:, :valid], v[:, :valid])
        np.testing.assert_allclose(np.asarray(got[:, :valid]),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("narrow", ["v", "k"])
    def test_packed_mixed_dtype_cotangents(self, narrow):
        """The packed dk/dv ride ONE kernel output; each half must come
        back in its own operand's dtype (custom_vjp cotangent check) AND
        at its own operand's precision — the shared output uses the
        WIDEST of the two dtypes so neither gradient is quantized through
        the other's width."""
        b, n, h, d = 1, 16, 2, 64
        q, k, v = (_rand(i + 90, (b, n, h, d)) for i in range(3))
        if narrow == "v":
            v = v.astype(jnp.bfloat16)
        else:
            k = k.astype(jnp.bfloat16)
        grads = jax.grad(
            lambda *a: jnp.sum(flash_attention(*a).astype(jnp.float32) ** 2),
            (0, 1, 2))(q, k, v)
        assert grads[1].dtype == k.dtype
        assert grads[2].dtype == v.dtype
        assert all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
                   for g in grads)
        # Precision pin for the WIDE operand's gradient: bitwise equal to
        # the folded path on the same inputs.
        import importlib
        fa = importlib.import_module("tpuic.kernels.flash_attention")
        bq, bk = fa._resolve_blocks(n, None, None)
        out, lse = fa._flash_fwd_packed(q, k, v, bq, bk, True, with_lse=True)
        g = jnp.ones((b, n, h, d), q.dtype)
        packed = fa._flash_bwd_packed(q, k, v, out, lse, g, bq, bk, True)
        folded = fa._flash_bwd(q, k, v, out, lse, g, bq, bk, True)
        wide = 1 if narrow == "v" else 2   # dk wide when v narrow, etc.
        np.testing.assert_array_equal(np.asarray(packed[wide]),
                                      np.asarray(folded[wide]))

    def test_packed_kill_switch(self, monkeypatch):
        """TPUIC_FLASH_PACKED=0 forces the folded path (chip-side escape
        hatch if Mosaic rejects the 4D-grid packed lowering)."""
        import importlib
        fa = importlib.import_module("tpuic.kernels.flash_attention")
        assert fa._use_packed(4, 64)
        monkeypatch.setenv("TPUIC_FLASH_PACKED", "0")
        assert not fa._use_packed(4, 64)
        assert not fa._use_packed(3, 64)  # odd heads never pack
        assert not fa._use_packed(4, 16)  # head_dim 16 never packs

    def test_bf16_stays_finite(self):
        b, n, h, d = 1, 16, 2, 8
        q, k, v = (20.0 * _rand(i, (b, n, h, d)).astype(jnp.bfloat16)
                   for i in range(3))
        out = flash_attention(q, k, v, block_q=8, block_k=8)
        assert out.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


def _conv_ref(x, w, scale, bias, strides, padding, relu):
    """Unfused reference: lax conv + BN-affine + ReLU."""
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=strides, padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = y * scale + bias
    return jnp.maximum(y, 0) if relu else y


class TestFusedConvBNRelu:
    """tpuic/kernels/conv_bn_relu.py: numerics parity atol 1e-4 /
    rtol 1e-4 (documented in ModelConfig.fused_conv_bn — the tap-matmul
    f32 accumulation order differs from XLA's convolution; measured
    ~1e-7 on the model zoo in float32)."""

    CASES = [
        # (h, w, cin, cout, k, stride, pad) — the ResNet shapes:
        (8, 8, 3, 16, 3, 1, 1),      # conv3x3 stride 1
        (9, 11, 4, 8, 3, 2, 1),      # conv3x3 stride 2, odd dims
        (32, 32, 3, 16, 7, 2, 3),    # the 7x7/s2 stem
        (8, 8, 16, 32, 1, 2, 0),     # downsample conv1x1 stride 2
        (8, 8, 16, 32, 1, 1, 0),     # bottleneck conv1x1
    ]

    def _case(self, key, h, w, cin, cout, k):
        rng = np.random.default_rng(key)
        x = jnp.asarray(rng.standard_normal((2, h, w, cin)), jnp.float32)
        wk = jnp.asarray(0.1 * rng.standard_normal((k, k, cin, cout)),
                         jnp.float32)
        sc = jnp.asarray(rng.standard_normal(cout), jnp.float32)
        bi = jnp.asarray(rng.standard_normal(cout), jnp.float32)
        return x, wk, sc, bi

    @pytest.mark.parametrize("h,w,cin,cout,k,s,p", CASES)
    def test_matches_unfused_reference(self, h, w, cin, cout, k, s, p):
        x, wk, sc, bi = self._case(h + k + s, h, w, cin, cout, k)
        got = fused_conv_bn_relu(x, wk, sc, bi, strides=s, padding=p)
        want = _conv_ref(x, wk, sc, bi, (s, s), ((p, p), (p, p)), True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_relu_off_for_residual_tail(self):
        """relu=False is the pre-residual-add case: negative values
        must survive."""
        x, wk, sc, bi = self._case(7, 8, 8, 4, 8, 3)
        got = fused_conv_bn_relu(x, wk, sc, bi, padding=1, relu=False)
        want = _conv_ref(x, wk, sc, bi, (1, 1), ((1, 1), (1, 1)), False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
        assert float(jnp.min(got)) < 0.0

    def test_under_jit_compiled_program(self):
        """'Compiled mode' on the CPU suite: the kernel inside one
        jitted program (interpret lowers through XLA; on TPU the same
        call compiles via Mosaic).  Values must match the eager
        interpret run bitwise — one lowering, two entry paths."""
        x, wk, sc, bi = self._case(11, 8, 8, 4, 8, 3)

        @jax.jit
        def prog(x, wk, sc, bi):
            return fused_conv_bn_relu(x, wk, sc, bi, strides=1, padding=1)

        eager = fused_conv_bn_relu(x, wk, sc, bi, strides=1, padding=1)
        np.testing.assert_array_equal(np.asarray(prog(x, wk, sc, bi)),
                                      np.asarray(eager))

    def test_fold_bn_matches_flax_batchnorm(self):
        """fold_bn must reproduce nn.BatchNorm(use_running_average)
        exactly: y = (x - mean) * gamma * rsqrt(var + eps) + beta."""
        rng = np.random.default_rng(3)
        c = 12
        x = jnp.asarray(rng.standard_normal((4, 5, 5, c)), jnp.float32)
        gamma = jnp.asarray(rng.standard_normal(c), jnp.float32)
        beta = jnp.asarray(rng.standard_normal(c), jnp.float32)
        mean = jnp.asarray(rng.standard_normal(c), jnp.float32)
        var = jnp.asarray(rng.random(c) + 0.1, jnp.float32)
        scale, bias = fold_bn(gamma, beta, mean, var, eps=1e-5)
        want = (x - mean) * (gamma * jax.lax.rsqrt(var + 1e-5)) + beta
        np.testing.assert_allclose(np.asarray(x * scale + bias),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)

    def test_output_dtype_follows_input(self):
        x, wk, sc, bi = self._case(13, 8, 8, 4, 8, 3)
        out = fused_conv_bn_relu(x.astype(jnp.bfloat16), wk, sc, bi,
                                 padding=1)
        assert out.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))

    @pytest.mark.parametrize("name,size", [
        ("resnet18-cifar", 32),
        # ~18 s CPU: plain resnet50 parity; the cifar and s2d params keep
        # fused-inference parity tier-1 for both conv layouts.
        pytest.param("resnet50", 64, marks=pytest.mark.slow),
        ("resnet50-s2d", 64)])
    def test_resnet_fused_inference_parity(self, name, size):
        """The model-zoo wiring (ModelConfig.fused_conv_bn): identical
        parameter structure (checkpoints interchangeable), inference
        parity within the documented atol, and the TRAIN path bitwise
        untouched (the fused branch must never engage when BN needs
        batch statistics)."""
        from tpuic.models import create_model

        base = create_model(name, 10, dtype="float32")
        fused = create_model(name, 10, dtype="float32",
                             fused_conv_bn=True)
        v = base.init(jax.random.key(0), jnp.zeros((2, size, size, 3)),
                      train=False)
        v2 = fused.init(jax.random.key(0), jnp.zeros((2, size, size, 3)),
                        train=False)
        assert (jax.tree_util.tree_structure(v)
                == jax.tree_util.tree_structure(v2))
        x = jax.random.normal(jax.random.key(1), (2, size, size, 3))
        a = base.apply(v, x, train=False)
        b = fused.apply(v, x, train=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        at, _ = base.apply(v, x, train=True, mutable=["batch_stats"])
        bt, _ = fused.apply(v, x, train=True, mutable=["batch_stats"])
        np.testing.assert_array_equal(np.asarray(at), np.asarray(bt))

    def test_config_plumb(self):
        """ModelConfig.fused_conv_bn reaches the ResNet module; the
        non-ResNet families accept and ignore the flag."""
        from tpuic.config import ModelConfig
        from tpuic.models import create_model, create_model_from_config

        m = create_model_from_config(ModelConfig(
            name="resnet18-cifar", num_classes=7, dtype="float32",
            fused_conv_bn=True))
        assert m.backbone.fused_inference is True
        # Non-ResNet backbones take the flag without blowing up.
        create_model("vit-tiny", 7, fused_conv_bn=True)
        create_model("efficientnet-b0", 7, fused_conv_bn=True)
        create_model("inceptionv3", 7, fused_conv_bn=True)


class TestFusedCrossEntropy:
    REF_WEIGHTS = jnp.array([3, 3, 10, 1, 4, 4, 5], jnp.float32)

    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    def test_matches_reference(self, smoothing):
        b, c = 37, 7  # non-multiple of block: exercises batch padding
        logits = 5.0 * _rand(0, (b, c))
        labels = jax.random.randint(jax.random.key(1), (b,), 0, c)
        mask = (jax.random.uniform(jax.random.key(2), (b,)) > 0.2
                ).astype(jnp.float32)
        got = fused_weighted_cross_entropy(
            logits, labels, self.REF_WEIGHTS, mask, smoothing, 16)
        want = weighted_cross_entropy(logits, labels, self.REF_WEIGHTS, mask,
                                      smoothing)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    def test_unweighted_unmasked(self):
        logits = _rand(3, (8, 10))
        labels = jax.random.randint(jax.random.key(4), (8,), 0, 10)
        got = fused_weighted_cross_entropy(logits, labels, block_b=8)
        want = weighted_cross_entropy(logits, labels)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-6)

    def test_gradients_match_reference(self):
        b, c = 20, 7
        logits = _rand(5, (b, c))
        labels = jax.random.randint(jax.random.key(6), (b,), 0, c)
        mask = jnp.ones((b,)).at[-3:].set(0.0)

        g1 = jax.grad(lambda x: fused_weighted_cross_entropy(
            x, labels, self.REF_WEIGHTS, mask, 0.0, 16))(logits)
        g2 = jax.grad(lambda x: weighted_cross_entropy(
            x, labels, self.REF_WEIGHTS, mask))(logits)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-5, atol=1e-6)
        # masked samples contribute no gradient
        assert np.abs(np.asarray(g1)[-3:]).max() == 0.0

    def test_under_jit_and_grad_composition(self):
        logits = _rand(7, (16, 7))
        labels = jax.random.randint(jax.random.key(8), (16,), 0, 7)

        @jax.jit
        def step(x):
            return jax.value_and_grad(
                lambda y: fused_weighted_cross_entropy(
                    y, labels, self.REF_WEIGHTS, None, 0.0, 8))(x)

        loss, grad = step(logits)
        assert np.isfinite(float(loss))
        assert grad.shape == logits.shape


class TestKernelWiring:
    def test_flash_vit_matches_dense_vit(self):
        from tpuic.models import create_model

        dense = create_model("vit-tiny", 7, dtype="float32",
                             attention="dense")
        flash = create_model("vit-tiny", 7, dtype="float32",
                             attention="flash")
        v = dense.init(jax.random.key(0), jnp.zeros((2, 16, 16, 3)),
                       train=False)
        x = jax.random.normal(jax.random.key(1), (2, 16, 16, 3))
        a = dense.apply(v, x, train=False)
        b = flash.apply(v, x, train=False)  # same params: only attn differs
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_flash_vit_s16_matches_dense_vit_packed_path(self):
        """vit-s16 has head_dim 64 / 6 heads — the shapes the lane-packed
        kernel dispatch covers (vit-tiny's head_dim 16 exercises the
        folded fallback above)."""
        import sys
        from tpuic.models import create_model

        fa = sys.modules["tpuic.kernels.flash_attention"]
        assert fa._use_packed(6, 64)
        dense = create_model("vit-s16", 5, dtype="float32",
                             attention="dense")
        flash = create_model("vit-s16", 5, dtype="float32",
                             attention="flash")
        v = dense.init(jax.random.key(0), jnp.zeros((1, 64, 64, 3)),
                       train=False)
        x = jax.random.normal(jax.random.key(1), (1, 64, 64, 3))
        a = dense.apply(v, x, train=False)
        b = flash.apply(v, x, train=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)

    @requires_shard_map
    def test_sharded_train_step_with_flash_and_fused_loss(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tpuic.config import MeshConfig, ModelConfig, OptimConfig
        from tpuic.data.synthetic import synthetic_batch
        from tpuic.models import create_model
        from tpuic.runtime.mesh import make_mesh
        from tpuic.train.optimizer import make_optimizer
        from tpuic.train.state import create_train_state
        from tpuic.train.step import make_train_step

        mesh = make_mesh(MeshConfig(), jax.devices())
        mcfg = ModelConfig(name="vit-tiny", num_classes=7, dtype="float32",
                           attention="flash")
        ocfg = OptimConfig(fused_loss=True)
        model = create_model(mcfg.name, mcfg.num_classes, dtype=mcfg.dtype,
                             attention=mcfg.attention, mesh=mesh)
        with mesh:
            state = create_train_state(model, make_optimizer(ocfg),
                                       jax.random.key(0), (16, 16, 16, 3))
            batch = synthetic_batch(16, 16, 7)
            sh = NamedSharding(mesh, P("data"))
            batch = {k: jax.device_put(v, sh) for k, v in batch.items()}
            step = make_train_step(ocfg, mcfg, mesh, donate=False)
            # The kernels must stay batch-parallel: an opaque pallas call
            # would force GSPMD to all-gather the sharded activations.
            hlo = step.lower(state, batch).compile().as_text()
            assert "all-gather" not in hlo, "pallas call got replicated"
            state2, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["grad_norm"]) > 0.0

    def test_unknown_attention_impl_raises(self):
        from tpuic.models import create_model

        with pytest.raises(ValueError, match="unknown attention impl"):
            create_model("vit-tiny", 7, attention="Flash")

    def test_unknown_loss_impl_raises(self):
        from tpuic.train.loss import classification_loss

        with pytest.raises(ValueError, match="unknown loss impl"):
            classification_loss(jnp.zeros((2, 3)), jnp.zeros((2,), jnp.int32),
                                impl="fused-typo")


def _masked_attention(q, k, v, causal, window):
    """Dense softmax attention under the band's mask, the key-value heads
    repeated over their groups: what the banded kernels must match."""
    n, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, t = np.arange(n)[:, None], np.arange(n)[None]
    seen = np.ones((n, n), bool)
    if causal or window is not None:
        seen &= t <= i
    if window is not None:
        seen &= t > i - window
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


class TestBandedFlashAttention:
    """``causal``, ``window`` and grouped key-value heads (PR 36): the
    kernels whose grids hold only the tiles with an unmasked pair."""

    # n = 64 in blocks of 16: window smaller than, equal to and larger
    # than the length; 50 and 72 are no multiple of a block; Hkv = H, H/2
    # and H/8; unequal blocks; grouped heads without any mask
    CASES = [
        (64, 4, 4, True, None, 16, 16), (64, 4, 2, True, 24, 16, 16),
        (64, 8, 1, True, 64, 16, 16), (64, 4, 2, False, 100, 16, 32),
        (50, 4, 2, True, 20, 16, 16), (72, 4, 1, True, 17, 32, 8),
        (50, 4, 2, False, None, 16, 16)]

    @pytest.mark.parametrize("n,h,hkv,causal,window,bq,bk", CASES)
    def test_forward_and_all_three_gradients_match_dense(
            self, n, h, hkv, causal, window, bq, bk):
        q = _rand(1, (2, n, h, 16))
        k, v = (_rand(i, (2, n, hkv, 16)) for i in (2, 3))
        w = _rand(4, (2, n, h, 16))

        def banded(q, k, v):
            return flash_attention(q, k, v, bq, bk, True, None, None, causal,
                                   window)
        np.testing.assert_allclose(
            np.asarray(banded(q, k, v)),
            np.asarray(_masked_attention(q, k, v, causal, window)),
            rtol=1e-5, atol=1e-5)
        got = jax.grad(lambda *a: jnp.sum(banded(*a) * w), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(_masked_attention(
            *a, causal, window) * w), (0, 1, 2))(q, k, v)
        for a, b in zip(got, want):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("n,bq,bk,causal,window,visited", [
        # a causal square of 8 x 8 blocks: the diagonal and below, 36
        (4096, 512, 512, True, None, 36),
        # a band of 1,024 over blocks of 512: rows 0 and 1 see 1 and 2
        # blocks, every later row its own, the one before and the far
        # edge's: 1 + 2 + 6 x 3 = 21
        (4096, 512, 512, True, 1024, 21),
        # blocks of 128: a causal square 32 x 33 / 2; a band 1 + ... + 8
        # then 24 rows of 9
        (4096, 128, 128, True, None, 528),
        (4096, 128, 128, True, 1024, 36 + 24 * 9),
        # the window implies the causal mask; one larger than the length
        # is the causal square
        (4096, 512, 512, False, 1024, 21), (4096, 512, 512, True, 8192, 36),
        # key blocks half the query blocks' size: 2 (j + 1) a row
        (2048, 512, 256, True, None, 2 + 4 + 6 + 8),
        # bidirectional: the whole square
        (4096, 512, 512, False, None, 64)])
    def test_the_grid_holds_the_tiles_counted_by_hand(self, n, bq, bk, causal,
                                                      window, visited):
        from tpuic.kernels.flash_attention import _band, blocks_visited
        assert blocks_visited(n, bq, bk, causal, window) == (
            visited, (n // bq) * (n // bk))
        # and it is the launched grid's own list, in both orders
        causal = causal or window is not None
        for by_key in (False, True):
            q_of, k_of, edge = _band(n, bq, bk, causal, window, n, by_key)
            assert len(q_of) == len(k_of) == len(edge) == visited
            own = k_of if by_key else q_of
            firsts = [i for i in range(visited) if edge[i] & 1]
            lasts = [i for i in range(visited) if edge[i] & 2]
            assert len(firsts) == len(lasts) == len(set(own.tolist()))
            assert list(own) == sorted(own)     # own blocks are contiguous

    def test_the_three_calls_carry_their_names_and_the_defaults_none(self):
        q = jax.ShapeDtypeStruct((2, 64, 4, 16), jnp.float32)
        k = jax.ShapeDtypeStruct((2, 64, 2, 16), jnp.float32)

        def text(k, **kw):
            return str(jax.make_jaxpr(jax.grad(
                lambda q, k, v: jnp.sum(flash_attention(
                    q, k, v, 16, 16, True, **kw)), (0, 1, 2)))(q, k, k))
        banded = text(k, causal=True, window=24)
        for name in ("banded_attention_fwd", "banded_attention_dq",
                     "banded_attention_dkv"):
            assert name in banded
        assert "banded_attention" not in text(q)

    def test_the_defaults_lower_to_the_text_they_did_before_the_band(self):
        """sha256 of the lowered forward + backward of the bidirectional
        path, taken on the parent of PR 36 with this installation's jax
        (folded layout at head size 16, packed at 64): an argument that
        is not passed changes nothing, to the byte. The text is jax
        0.9.0's: after an upgrade, unpack the parent (``git archive
        43728ef | tar -x -C <dir>``), lower this same function there
        under the new jax and write its digests here; if they then
        differ from this tree's, the band did change the default path."""
        import hashlib
        for shape, want in (((2, 197, 12, 64), "14673288ae7fd1df"),
                            ((2, 100, 4, 16), "3b4b7045a9fd16fe")):
            x = jax.ShapeDtypeStruct(shape, jnp.float32)

            def loss(q, k, v):      # the text carries the function's name
                return jnp.sum(flash_attention(q, k, v, None, None, True))
            text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(x, x, x).as_text()
            assert hashlib.sha256(text.encode()).hexdigest()[:16] == want

    def test_heads_that_do_not_divide_are_refused(self):
        q, k = _rand(1, (1, 16, 4, 8)), _rand(2, (1, 16, 3, 8))
        with pytest.raises(ValueError, match="whole group"):
            flash_attention(q, k, k, 8, 8, True, None, None, True)

    def test_on_the_chip_a_head_is_a_whole_lane_tile(self):
        q = _rand(1, (1, 16, 4, 8))
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, q, q, 8, 8, False, None, None, True)
