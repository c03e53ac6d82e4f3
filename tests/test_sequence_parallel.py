"""Sequence-parallel attention exactness on the 8-device mesh: ring
attention and Ulysses must reproduce full (single-device) attention on
the gathered sequence, bidirectional and causal."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from imagent_tpu.cluster import DATA_AXIS, make_mesh
from imagent_tpu.ops.attention import dot_product_attention
from imagent_tpu.parallel.ring_attention import ring_attention
from imagent_tpu.parallel.ulysses import ulysses_attention

B, N, H, D = 2, 64, 8, 16  # N_local = 8 on the 8-device mesh


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    return tuple(
        jnp.asarray(rng.normal(size=(B, N, H, D)).astype(np.float32))
        for _ in range(3))


def _full_reference(q, k, v, causal):
    mask = jnp.tril(jnp.ones((N, N), bool))[None, None] if causal else None
    return dot_product_attention(q, k, v, mask=mask)


def _sharded(fn, causal):
    mesh = make_mesh()
    spec = P(None, DATA_AXIS)  # shard the sequence dimension

    def per_device(q, k, v):
        return fn(q, k, v, DATA_AXIS, causal=causal)

    return jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(qkv, causal):
    q, k, v = qkv
    got = _sharded(ring_attention, causal)(q, k, v)
    want = _full_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(qkv, causal):
    q, k, v = qkv
    got = _sharded(ulysses_attention, causal)(q, k, v)
    want = _full_reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_long_sequence_memory_shape():
    """Ring attention never materializes the (N, N) matrix — per-device
    peak is (B, H, N_local, N_local). Run a longer sequence to exercise
    multiple rotations with bf16 inputs."""
    rng = np.random.default_rng(1)
    n = 256
    q, k, v = (jnp.asarray(rng.normal(size=(1, n, 4, 8)).astype(np.float32),
                           dtype=jnp.bfloat16) for _ in range(3))
    got = _sharded(ring_attention, False)(q, k, v)
    assert got.shape == (1, n, 4, 8)
    assert got.dtype == jnp.bfloat16
    want = dot_product_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, dtype=np.float32), np.asarray(want, np.float32),
        rtol=0.05, atol=0.05)


def test_ulysses_requires_divisible_heads(qkv):
    q, k, v = qkv
    q3 = q[:, :, :3]  # 3 heads, not divisible by 8
    with pytest.raises(Exception):
        _sharded(ulysses_attention, False)(q3, k[:, :, :3], v[:, :, :3])
