"""What the chip's compiler accepts — asked here, without the chip.

The TPU compiler is installed in this sandbox and compiles for a chip
that is DESCRIBED, not attached (``get_topology_desc`` — section 2 of
the on-chip-measurement guide): every Pallas kernel the engine can
select is lowered ``interpret=False`` at the real widths of the models
that use it, forward and backward. Interpret-mode tests cannot see what
these see — a primitive with no Mosaic lowering (the exact-GELU ``erf``
of ``ops/fused_mlp.py``, refused until PR 21), a misaligned tile, a
working set over VMEM. About a second each, shapes not arrays, nothing
runs: a compile that passes here is NOT a chip run and is never
reported as one. Skipped where the topology cannot be described.

Plus the other half of "no hidden CPU path": ``--backend=tpu`` on a
machine with no chip is a fatal-config exit that names what it found.
"""

import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # compiler logs: off

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from imagent_tpu.ops.flash_attention import flash_attention  # noqa: E402
from imagent_tpu.ops.fused_block import fused_bottleneck  # noqa: E402
from imagent_tpu.ops.fused_mlp import (  # noqa: E402
    fused_mlp_block, pick_block_rows,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip's sharding. The persistent compilation
    cache is off for the whole suite (conftest), which these compiles
    need: an entry written for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    assert not jax.config.jax_enable_compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _sum_grad(fn, n_args):
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                    argnums=tuple(range(n_args)))


def _flash(sharding, heads, backward):
    # ViT-B/16 and ViT-L/16 at 224px: N = 196 patches + cls.
    q = _sds(sharding, (8, 197, heads, 64))

    def fn(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    return (_sum_grad(fn, 3) if backward else fn), (q, q, q)


def _fused_mlp(sharding, c, hw, backward, rows=None):
    # ConvNeXt-T stage geometry; the row tile defaults to the one the
    # engine's own VMEM model picks (fused_block_rows ->
    # pick_block_rows).
    x = _sds(sharding, (8, hw, hw, c))
    args = (x, x, _sds(sharding, (c,)), _sds(sharding, (c,)),
            _sds(sharding, (c, 4 * c)), _sds(sharding, (4 * c,)),
            _sds(sharding, (4 * c, c)), _sds(sharding, (c,)),
            _sds(sharding, (c,)))
    rows = rows or pick_block_rows(c, 2)

    def fn(*a):
        return fused_mlp_block(*a, block_rows=rows, interpret=False)

    return (_sum_grad(fn, 9) if backward else fn), args


def _bottleneck(sharding):
    # ResNet-50 layer4 identity block: 7x7, C=2048, F=512.
    hw, c, f = 7, 2048, 512
    f32 = jnp.float32
    args = (_sds(sharding, (8, hw, hw, c)), _sds(sharding, (c, f)),
            _sds(sharding, (f,), f32), _sds(sharding, (3, 3, f, f)),
            _sds(sharding, (f,), f32), _sds(sharding, (f, c)),
            _sds(sharding, (c,), f32))

    def fn(*a):
        return fused_bottleneck(*a, batch_tile=8, interpret=False)

    return fn, args


CASES = {
    "flash-fwd-vit_b16": lambda s: _flash(s, 12, False),
    "flash-bwd-vit_b16": lambda s: _flash(s, 12, True),
    "flash-fwd-vit_l16": lambda s: _flash(s, 16, False),
    "flash-bwd-vit_l16": lambda s: _flash(s, 16, True),
    "fused_mlp-fwd-c96": lambda s: _fused_mlp(s, 96, 56, False),
    "fused_mlp-bwd-c96": lambda s: _fused_mlp(s, 96, 56, True),
    "fused_mlp-fwd-c192": lambda s: _fused_mlp(s, 192, 28, False),
    "fused_mlp-bwd-c192": lambda s: _fused_mlp(s, 192, 28, True),
    "fused_mlp-fwd-c384": lambda s: _fused_mlp(s, 384, 14, False),
    "fused_mlp-bwd-c384": lambda s: _fused_mlp(s, 384, 14, True),
    "fused_bottleneck-r50-layer4": _bottleneck,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    fn, args = CASES[case](v5e)
    compiled = jax.jit(fn).lower(*args).compile()
    # The kernel itself is in the program (not an XLA fallback).
    assert "tpu_custom_call" in compiled.as_text()


def test_vmem_model_never_says_fits_where_the_compiler_refuses(v5e):
    """``pick_block_rows`` (the --fused-mlp auto/on decision) against
    the compiler: at C=768 the model finds no tile that fits — and the
    compiler indeed refuses the backward even at a 64-row tile (VMEM
    exhausted), so the fallback to the unfused path there is forced,
    not conservative. Every width the model DOES admit compiles (the
    fused_mlp cases above)."""
    assert pick_block_rows(768, 2) is None
    bwd, args = _fused_mlp(v5e, 768, 7, True, rows=64)
    with pytest.raises(Exception, match="(?i)vmem"):
        jax.jit(bwd).lower(*args).compile()


def test_backend_tpu_without_a_chip_is_fatal_config(tmp_path):
    """``--backend=tpu`` where JAX can only give the CPU: exit 78
    (fatal-config, not retryable), the message names the platform it
    found, and nothing trained."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu", "--backend=tpu",
         "--arch=resnet18", "--dataset=synthetic", "--image-size=16",
         "--num-classes=4", "--batch-size=4", "--synthetic-size=32",
         "--epochs=1", "--workers=0",
         f"--log-dir={tmp_path / 'tb'}", f"--ckpt-dir={tmp_path / 'ck'}"],
        capture_output=True, text=True, timeout=300, env=env, cwd=_REPO)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 78, out[-2000:]
    assert "--backend=tpu was requested" in out
    assert "initialized the 'cpu' platform" in out
    assert "Epoch 1" not in out and not (tmp_path / "tb").exists()
