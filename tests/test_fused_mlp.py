"""Fused ConvNeXt MLP kernel (ops/fused_mlp.py): forward + backward
parity vs the unfused block in interpret mode on CPU (both dtypes),
the VMEM-overflow / drop-path fallbacks, --fused-mlp decision logic,
and DDP-equivalence of the fused path through make_train_step — the
ISSUE-7 acceptance coverage for the first custom-VJP Pallas kernel on
the training hot path since flash attention."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imagent_tpu.models.convnext import ConvNeXt, ConvNeXtBlock
from imagent_tpu.ops.fused_mlp import (
    fused_block_rows, fused_mlp_block, fused_mlp_plan, fused_vmem_bytes,
    pick_block_rows, reference_mlp_block,
)

B, H, W, C = 2, 5, 7, 24  # rows = 70: exercises the pad-to-tile path


def _kernel_args(rng, dtype):
    mk = lambda shape, dt=jnp.float32: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * 0.5, dt)
    return (mk((B, H, W, C), dtype), mk((B, H, W, C), dtype),
            mk((C,)), mk((C,)), mk((C, 4 * C)), mk((4 * C,)),
            mk((4 * C, C)), mk((C,)), mk((C,)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_forward_parity(dtype):
    args = _kernel_args(np.random.default_rng(0), dtype)
    got = fused_mlp_block(*args, block_rows=16, interpret=True)
    want = reference_mlp_block(*args)
    assert got.dtype == want.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_backward_parity(dtype):
    """The custom VJP (remat-in-kernel) must match autodiff through the
    unfused reference for EVERY argument's cotangent."""
    args = _kernel_args(np.random.default_rng(1), dtype)

    def loss_fused(a):
        return jnp.sum(jnp.square(
            fused_mlp_block(*a, block_rows=16,
                            interpret=True).astype(jnp.float32)))

    def loss_ref(a):
        return jnp.sum(jnp.square(
            reference_mlp_block(*a).astype(jnp.float32)))

    gf = jax.grad(loss_fused)(args)
    gr = jax.grad(loss_ref)(args)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for name, a, b in zip(
            "resid h ln_scale ln_bias w1 b1 w2 b2 gamma".split(), gf, gr):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        denom = np.max(np.abs(b)) + 1e-6
        assert np.max(np.abs(a - b)) / denom < tol, name


def _block_apply(fused, dtype, drop_prob=0.0, train=False, rngs=None):
    rng = np.random.default_rng(2)
    block = ConvNeXtBlock(dim=C, dtype=dtype, fused_mlp=fused,
                          drop_prob=drop_prob)
    x = jnp.asarray(rng.normal(size=(B, H, W, C)), dtype)
    v = ConvNeXtBlock(dim=C, dtype=dtype).init(
        jax.random.key(0), x, train=False)
    return block.apply(v, x, train=train, rngs=rngs), v, x, block


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_module_parity(dtype):
    """The real flax Block under --fused-mlp on == off, same params."""
    got, v, x, _ = _block_apply("on", dtype)
    want = ConvNeXtBlock(dim=C, dtype=dtype, fused_mlp="off").apply(
        v, x, train=False)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_block_module_grad_parity():
    """d loss / d params through the fused Block == unfused, f32."""
    _, v, x, _ = _block_apply("on", jnp.float32)

    def loss(params, fused):
        out = ConvNeXtBlock(dim=C, dtype=jnp.float32,
                            fused_mlp=fused).apply(
            {"params": params}, x, train=True)
        return jnp.sum(jnp.square(out))

    gf = jax.grad(loss)(v["params"], "on")
    gr = jax.grad(loss)(v["params"], "off")
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(gf),
            jax.tree_util.tree_leaves_with_path(gr)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(pa))


def test_param_tree_identical_across_modes():
    """The fused lowering must not change the checkpoint surface."""
    x = jnp.zeros((1, 4, 4, C))
    trees = [
        jax.tree_util.tree_structure(
            ConvNeXtBlock(dim=C, fused_mlp=m).init(
                jax.random.key(0), x, train=False))
        for m in ("off", "on", "auto")]
    assert trees[0] == trees[1] == trees[2]


def test_vmem_overflow_falls_back():
    """C=768's backward accumulators exceed VMEM at any tile: the
    decision is None even under 'on', and the Block silently runs the
    unfused path with identical numerics."""
    assert pick_block_rows(768, itemsize=2, backward=True) is None
    assert fused_block_rows("on", 768) is None
    # The direct API refuses instead of compiling an over-budget kernel
    # (a Mosaic compile-time wedge on TPU) when no tile can fit.
    big = jnp.zeros((1, 2, 2, 768), jnp.bfloat16)
    with pytest.raises(ValueError, match="exceeds the VMEM budget"):
        fused_mlp_block(big, big, *(jnp.zeros(s) for s in
                                    ((768,), (768,), (768, 3072),
                                     (3072,), (3072, 768), (768,),
                                     (768,))))
    # The coarse model is monotone in both c and block_rows.
    assert fused_vmem_bytes(96, 256) < fused_vmem_bytes(192, 256)
    assert fused_vmem_bytes(96, 128) < fused_vmem_bytes(96, 256)

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, 2, 2, 768)), jnp.float32)
    v = ConvNeXtBlock(dim=768).init(jax.random.key(0), x, train=False)
    got = ConvNeXtBlock(dim=768, fused_mlp="on").apply(v, x, train=False)
    want = ConvNeXtBlock(dim=768, fused_mlp="off").apply(v, x,
                                                         train=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_auto_requires_tpu_backend():
    """'auto' never fuses on the CPU CI backend (interpret mode would
    be orders of magnitude slower than XLA); 'on' does (that is how CI
    exercises the kernel)."""
    assert jax.default_backend() != "tpu"
    assert fused_block_rows("auto", 96) is None
    assert fused_block_rows("on", 96) is not None
    assert fused_block_rows("off", 96) is None


def test_drop_path_falls_back():
    """An active stochastic-depth mask uses the unfused path (the
    kernel fuses the production rate-0.0 block): fused vs unfused agree
    exactly under the same droppath rng."""
    assert fused_block_rows("on", C, dropping=True) is None
    rngs = {"droppath": jax.random.key(9)}
    got, v, x, _ = _block_apply("on", jnp.float32, drop_prob=0.5,
                                train=True, rngs=rngs)
    want = ConvNeXtBlock(dim=C, dtype=jnp.float32, fused_mlp="off",
                         drop_prob=0.5).apply(v, x, train=True,
                                              rngs=rngs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # Eval mode: no mask is active, so the fused path engages again.
    got_eval, _, _, _ = _block_apply("on", jnp.float32, drop_prob=0.5,
                                     train=False)
    assert np.all(np.isfinite(np.asarray(got_eval)))


class _SeedBlock(nn.Module):
    """The seed ConvNeXt block, module chain in the ORIGINAL source
    order (layer_scale created last) — the bit-for-bit oracle for the
    --fused-mlp off regression guard."""

    dim: int

    @nn.compact
    def __call__(self, x):
        from imagent_tpu.models.convnext import trunc_init

        y = nn.Conv(self.dim, (7, 7), padding=((3, 3), (3, 3)),
                    feature_group_count=self.dim, use_bias=True,
                    kernel_init=trunc_init, name="dwconv")(x)
        y = nn.LayerNorm(epsilon=1e-6, name="norm")(y)
        y = nn.Dense(4 * self.dim, kernel_init=trunc_init,
                     name="pwconv1")(y)
        y = nn.gelu(y, approximate=False)
        y = nn.Dense(self.dim, kernel_init=trunc_init, name="pwconv2")(y)
        gamma = self.param("layer_scale",
                           nn.initializers.constant(1e-6), (self.dim,))
        return x + y * gamma


def test_off_is_bit_for_bit_todays_path():
    """ISSUE-7 acceptance: --fused-mlp off preserves today's numerics
    bit-for-bit. The default Block, the explicit 'off' Block, and the
    seed-order module chain (layer_scale created AFTER the MLP — the
    pre-round-6 source order) must agree exactly on both the init
    param VALUES (flax derives param rngs from the path, not creation
    order — pinned here) and the apply output."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(B, H, W, C)), jnp.float32)

    block = ConvNeXtBlock(dim=C)
    v = block.init(jax.random.key(1), x, train=False)
    v_seed = _SeedBlock(dim=C).init(jax.random.key(1), x)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(v),
            jax.tree_util.tree_leaves_with_path(v_seed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(pa))

    want = _SeedBlock(dim=C).apply(v, x)
    got_default = block.apply(v, x, train=False)
    got_off = ConvNeXtBlock(dim=C, fused_mlp="off").apply(
        v, x, train=False)
    np.testing.assert_array_equal(np.asarray(got_default),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_off), np.asarray(want))


def test_decision_validation_and_plan():
    with pytest.raises(ValueError, match="fused-mlp"):
        fused_block_rows("yes", 96)
    plan = fused_mlp_plan("on", (96, 192, 384, 768))
    assert plan[96] is not None and plan[192] is not None
    assert plan[768] is None  # backward accumulators exceed VMEM
    assert set(plan) == {96, 192, 384, 768}


def test_full_model_parity_with_remat():
    """Whole ConvNeXt (2 stages, downsample between) fused vs unfused,
    including under jax.checkpoint (remat wraps the custom-VJP kernel
    on the backward): forward parity + finite grads."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 16, 16, 3)), jnp.float32)
    kw = dict(depths=(1, 1), dims=(16, 32), num_classes=5,
              dtype=jnp.float32)
    v = ConvNeXt(**kw).init(jax.random.key(0), x, train=False)
    want = ConvNeXt(**kw).apply(v, x, train=False)
    got = ConvNeXt(**kw, fused_mlp="on").apply(v, x, train=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    def loss(params, fused, remat):
        out = ConvNeXt(**kw, fused_mlp=fused, remat=remat).apply(
            {"params": params}, x, train=True,
            mutable=["intermediates"])[0]
        return jnp.sum(jnp.square(out))

    g_fused = jax.grad(loss)(v["params"], "on", True)
    g_ref = jax.grad(loss)(v["params"], "off", False)
    for (pa, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_fused),
            jax.tree_util.tree_leaves_with_path(g_ref)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(pa))


class _FusedCNN(nn.Module):
    """Stem conv -> fused ConvNeXt block -> GAP -> head: the smallest
    model that puts the Pallas kernel + custom VJP on the production
    train-step path."""

    fused: str = "on"

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = nn.Conv(16, (3, 3))(x)
        x = ConvNeXtBlock(dim=16, fused_mlp=self.fused,
                          name="block")(x, train=train)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(8)(x)


def test_ddp_equivalence_fused_train_step():
    """The DDP-equivalence invariant (test_train.py) holds with the
    fused kernel inside make_train_step: the 8-way sharded step's
    pmean'd gradients + shared SGD update == serial per-shard grads on
    the same batch."""
    from imagent_tpu.cluster import make_mesh
    from imagent_tpu.ops import softmax_cross_entropy
    from imagent_tpu.train import (
        create_train_state, make_optimizer, make_train_step,
        replicate_state, shard_batch,
    )

    batch, size = 16, 16
    mesh = make_mesh(model_parallel=1)
    model = _FusedCNN()
    opt = make_optimizer(momentum=0.9, weight_decay=1e-4)
    state = replicate_state(
        create_train_state(model, jax.random.key(0), size, opt), mesh)
    host_state = jax.device_get(state)
    rng = np.random.default_rng(5)
    images = rng.normal(size=(batch, size, size, 3)).astype(np.float32)
    labels = rng.integers(0, 8, size=(batch,)).astype(np.int32)

    def shard_loss(params, x, y):
        logits = model.apply({"params": params}, x, train=True)
        return softmax_cross_entropy(logits, y).mean()

    n_shards, per = 8, batch // 8
    grads_acc = None
    for s in range(n_shards):
        g = jax.grad(shard_loss)(
            host_state.params,
            jnp.asarray(images[s * per:(s + 1) * per]),
            jnp.asarray(labels[s * per:(s + 1) * per]))
        grads_acc = g if grads_acc is None else jax.tree.map(
            jnp.add, grads_acc, g)
    grads_ref = jax.tree.map(lambda a: a / n_shards, grads_acc)

    lr, wd = 0.1, 1e-4
    expect = jax.tree.map(lambda p, g: p - lr * (g + wd * p),
                          host_state.params, grads_ref)

    step = make_train_step(model, opt, mesh)
    gi, gl = shard_batch(mesh, images, labels)
    new_state, metrics = step(state, gi, gl, np.float32(lr))
    assert np.asarray(metrics)[3] == batch  # a real (finite) step
    got = jax.device_get(new_state.params)
    for (pa, e), (_, g) in zip(
            jax.tree_util.tree_leaves_with_path(expect),
            jax.tree_util.tree_leaves_with_path(got)):
        np.testing.assert_allclose(
            np.asarray(e), np.asarray(g), rtol=1e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(pa))


def test_kernel_erf_polynomial_matches_lax_erf():
    """The in-kernel erf (erf has no Pallas TPU lowering; the kernel
    evaluates XLA's own f32 rational polynomial from mul/add/div/clamp)
    against ``jax.lax.erf``: within a few ulp of the result everywhere
    (both saturate to +-1 within one ulp), and exact-GELU value +
    derivative built on it
    match ``nn.gelu(approximate=False)`` and its autodiff."""
    from imagent_tpu.ops.fused_mlp import _erf, _gelu, _gelu_grad

    x = jnp.asarray(np.concatenate([
        np.linspace(-8.0, 8.0, 400_001),
        np.random.default_rng(0).normal(size=100_000) * 2.0,
    ]).astype(np.float32))
    assert float(jnp.max(jnp.abs(_erf(x) - jax.lax.erf(x)))) <= 6e-7
    assert abs(float(_erf(jnp.float32(9.0))) - 1.0) <= 2e-7
    assert abs(float(_erf(jnp.float32(-9.0))) + 1.0) <= 2e-7
    np.testing.assert_allclose(
        np.asarray(_gelu(x)), np.asarray(nn.gelu(x, approximate=False)),
        rtol=0, atol=2e-6)
    want = jax.vmap(jax.grad(lambda a: nn.gelu(a, approximate=False)))(x)
    np.testing.assert_allclose(np.asarray(_gelu_grad(x)),
                               np.asarray(want), rtol=0, atol=2e-6)


def test_interpret_is_explicit_or_follows_the_platform(monkeypatch):
    """``interpret=None`` compiles on the TPU and interprets only on
    the CPU platform (which tests and rehearsals select explicitly);
    any other platform is refused, never quietly interpreted. An
    explicit bool always wins."""
    from imagent_tpu.ops import resolve_interpret

    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(None) is True  # this suite runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu' platform"):
        resolve_interpret(None)
    assert resolve_interpret(True) is True
