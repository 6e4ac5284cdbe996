"""Parallelism-layer tests on the virtual 8-device CPU mesh (SURVEY.md §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_tpu.parallel import (
    MeshConfig,
    make_mesh,
    ring_attention,
    logical_to_spec,
    DEFAULT_RULES,
)
from determined_tpu.parallel.mesh import validate_divisibility
from determined_tpu.parallel.pipeline import pipeline_apply
from determined_tpu.parallel.ring import make_ring_attention, reference_attention
from determined_tpu.parallel.ulysses import make_ulysses_attention


def test_mesh_construction(devices8):
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, tensor=2), devices8)
    assert mesh.shape["data"] == 2
    assert mesh.shape["fsdp"] == 2
    assert mesh.shape["tensor"] == 2
    assert mesh.shape["context"] == 1
    validate_divisibility(mesh, global_batch=8)
    with pytest.raises(ValueError):
        validate_divisibility(mesh, global_batch=6)


def test_mesh_infer_axis(devices8):
    mesh = make_mesh(MeshConfig(tensor=2), devices8)  # data inferred = 4
    assert mesh.shape["data"] == 4


def test_mesh_bad_config(devices8):
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(data=3, tensor=2), devices8)


def test_logical_to_spec():
    spec = logical_to_spec(("batch", "sequence", "heads", None), DEFAULT_RULES)
    assert spec == jax.sharding.PartitionSpec(("data", "fsdp"), "context", "tensor", None)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(devices8, causal):
    mesh = make_mesh(MeshConfig(data=2, context=4), devices8)
    b, s, h, d = 4, 32, 4, 16
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)

    ring = make_ring_attention(mesh, causal=causal)
    got = jax.jit(ring)(q, k, v)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_zigzag_indices_roundtrip():
    from determined_tpu.parallel.ring import inverse_permutation, zigzag_indices

    perm = zigzag_indices(16, 4)
    # Device 0 owns chunks 0 and 7, device 1 chunks 1 and 6, ...
    assert list(perm[:4]) == [0, 1, 14, 15]
    assert list(perm[4:8]) == [2, 3, 12, 13]
    inv = inverse_permutation(perm)
    np.testing.assert_array_equal(perm[inv], np.arange(16))
    with pytest.raises(ValueError, match="divisible"):
        zigzag_indices(12, 4)  # 12 % 8 != 0


def test_ring_attention_contiguous_layout_matches(devices8):
    """The explicit contiguous layout (for pipelines that can't reorder
    tokens) stays exact, now with skip-instead-of-discard steps."""
    mesh = make_mesh(MeshConfig(data=2, context=4), devices8)
    b, s, h, d = 2, 32, 2, 16
    key = jax.random.PRNGKey(3)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, h, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, h, d), jnp.float32)
    ring = make_ring_attention(mesh, causal=True, zigzag=False)
    got = jax.jit(ring)(q, k, v)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_ring_attention_nonpow2_chunks(devices8):
    """Half-chunk lengths that no power-of-two block divides: the inner
    flash block shrinks to a divisor instead of raising (the einsum ring
    this replaced had no length constraint)."""
    mesh = make_mesh(MeshConfig(data=1, context=4), devices8[:4])
    b, s, h, d = 2, 48, 2, 8  # local 12, zigzag half-chunk 6
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, h, d))
    v = jax.random.normal(kv, (b, s, h, d))
    got = jax.jit(make_ring_attention(mesh, causal=True))(q, k, v)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_ring_attention_odd_seq_falls_back(devices8):
    """Seq not divisible by 2*ring: the wrapper silently uses the exact
    contiguous path instead of failing."""
    mesh = make_mesh(MeshConfig(data=1, context=4), devices8[:4])
    b, s, h, d = 2, 20, 2, 8  # 20 % 8 != 0, but 20 % 4 == 0
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, h, d))
    v = jax.random.normal(kv, (b, s, h, d))
    got = jax.jit(make_ring_attention(mesh, causal=True))(q, k, v)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_ring_attention_grads_match(devices8):
    mesh = make_mesh(MeshConfig(data=1, context=4), devices8[:4])
    b, s, h, d = 2, 16, 2, 8
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, h, d))
    v = jax.random.normal(kv, (b, s, h, d))

    ring = make_ring_attention(mesh, causal=True)
    g_ring = jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(reference_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(devices8, causal):
    mesh = make_mesh(MeshConfig(data=2, context=4), devices8)
    b, s, h, d = 2, 32, 8, 16  # heads divisible by context=4
    key = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d))
    k = jax.random.normal(kk, (b, s, h, d))
    v = jax.random.normal(kv, (b, s, h, d))

    uly = make_ulysses_attention(mesh, causal=causal)
    got = jax.jit(uly)(q, k, v)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_pipeline_matches_sequential(devices8):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_stages, n_micro, mb, dim = 4, 8, 2, 16
    mesh = make_mesh(MeshConfig(data=1, pipeline=4), devices8[:4])
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (n_stages, dim, dim)) / np.sqrt(dim)
    x = jax.random.normal(jax.random.PRNGKey(4), (n_micro, mb, dim))

    def stage_fn(w_stage, act):
        return jnp.tanh(act @ w_stage)

    def piped(w, x):
        # shard_map hands each device its [1, dim, dim] stage slice.
        return pipeline_apply(
            lambda p, a: stage_fn(p[0], a), w, x, axis_name="pipeline"
        )

    fn = shard_map(
        piped,
        mesh=mesh,
        in_specs=(P("pipeline"), P()),
        out_specs=P(),
        check_vma=False,
    )
    got = jax.jit(fn)(w, x)

    want = x
    for s in range(n_stages):
        want = jax.vmap(lambda a: stage_fn(w[s], a))(want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


class TestMultisliceMesh:
    def test_two_virtual_slices(self, devices8):
        from determined_tpu.parallel.mesh import MeshConfig, make_multislice_mesh

        # 2 "slices" of 4 devices: per-slice mesh data=2 x tensor=2, data
        # multiplied across slices -> global data=4.
        mesh = make_multislice_mesh(
            MeshConfig(data=2, tensor=2), dcn_data=2, devices=devices8
        )
        assert mesh.shape["data"] == 4 and mesh.shape["tensor"] == 2

    def test_single_slice_falls_back(self, devices8):
        from determined_tpu.parallel.mesh import MeshConfig, make_multislice_mesh

        mesh = make_multislice_mesh(
            MeshConfig(data=8), dcn_data=1, devices=devices8
        )
        assert mesh.shape["data"] == 8

    def test_sharded_step_on_multislice_mesh(self, devices8):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from determined_tpu.parallel.mesh import MeshConfig, make_multislice_mesh

        mesh = make_multislice_mesh(
            MeshConfig(data=2, fsdp=2), dcn_data=2, devices=devices8
        )
        x = jax.device_put(
            jnp.arange(32.0).reshape(8, 4),
            NamedSharding(mesh, P(("data", "fsdp"))),
        )
        y = jax.jit(lambda a: (a * 2).sum())(x)
        assert float(y) == float(jnp.arange(32.0).sum() * 2)
