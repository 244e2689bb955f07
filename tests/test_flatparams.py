"""FlatParams: flatten/unflatten round-trips, padding, spec caching, and
the kernel geometry ``flat_geometry`` sizes from d."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.zo_axpy import BLOCK_ROWS, LANES
from repro.utils.flatparams import flat_geometry, flat_spec, flatten, unflatten
from repro.utils.tree import tree_size


def _mixed_tree():
    return {
        "w": jnp.asarray(np.random.default_rng(0).normal(size=(17, 5)),
                         jnp.float32),
        "emb": jnp.asarray(np.random.default_rng(1).normal(size=(3, 4, 2)),
                           jnp.bfloat16),
        "b": jnp.arange(7, dtype=jnp.float32),
        "nested": {"s": jnp.asarray([[2.5]], jnp.float32)},
    }


def test_round_trip_identity_mixed_dtypes():
    tree = _mixed_tree()
    spec = flat_spec(tree, block=256)
    buf = flatten(tree, spec)
    out = unflatten(buf, spec)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        # bf16 → fp32 → bf16 is exact, fp32 passes through untouched
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_padding_geometry():
    tree = _mixed_tree()
    d = tree_size(tree)
    spec = flat_spec(tree, block=256)
    assert spec.d == d
    assert spec.n_pad % 256 == 0 and 0 <= spec.n_pad - d < 256
    buf = flatten(tree, spec)
    assert buf.shape == (spec.n_pad,)
    # pad region zeroed
    np.testing.assert_array_equal(np.asarray(buf[spec.d:]), 0.0)


def test_flat_index_convention_matches_leaf_order():
    """buf[offset:offset+size] IS the leaf, in traversal order — the index
    the counter-based direction convention is keyed on."""
    tree = _mixed_tree()
    spec = flat_spec(tree, block=128)
    buf = flatten(tree, spec)
    leaves = jax.tree.leaves(tree)
    for leaf, off, sz in zip(leaves, spec.offsets, spec.sizes):
        np.testing.assert_array_equal(
            np.asarray(buf[off:off + sz]),
            np.asarray(leaf.reshape(-1), np.float32))


def test_spec_is_cached():
    tree = _mixed_tree()
    s1 = flat_spec(tree, block=256)
    s2 = flat_spec(tree, block=256)
    assert s1 is s2
    s3 = flat_spec(tree, block=512)
    assert s3 is not s1 and s3.n_pad % 512 == 0


def test_unflatten_accepts_unpadded_buffer():
    """unflatten only needs the first d elements (reference-path use)."""
    tree = _mixed_tree()
    spec = flat_spec(tree, block=256)
    buf = flatten(tree, spec)[:spec.d]
    out = unflatten(buf, spec)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_flatten_inside_jit():
    tree = _mixed_tree()
    spec = flat_spec(tree, block=256)

    @jax.jit
    def rt(t):
        return unflatten(flatten(t, spec), spec)

    out = rt(tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# -- kernel geometry sized from d -------------------------------------------


def _params_of(d):
    """A parameter pytree of d scalars, as shapes only."""
    half = d // 2
    return {"a": jax.ShapeDtypeStruct((half,), jnp.float32),
            "b": jax.ShapeDtypeStruct((d - half,), jnp.float32)}


@pytest.mark.parametrize("buf_dtype,tile", [("float32", 8), ("bfloat16", 16)])
@pytest.mark.parametrize("d", [900, 7850, 50090, 65536, 65537, 1663370])
def test_derived_geometry_pads_under_a_tile_per_step(d, buf_dtype, tile):
    """block_rows=0: blocks of at most 512 rows, a whole number of sublane
    tiles, and under one tile of padding per grid step."""
    spec, br = flat_geometry(_params_of(d), 0, buf_dtype=buf_dtype)
    assert spec.d == d and spec.buf_dtype == buf_dtype
    assert 0 < br <= BLOCK_ROWS and br % tile == 0
    per = br * LANES
    assert spec.n_pad % per == 0 and spec.n_pad >= d
    steps = spec.n_pad // per
    assert spec.n_pad - d < steps * tile * LANES
    # never more grid steps than the 512-row block needs
    assert steps == -(-d // (BLOCK_ROWS * LANES))


@pytest.mark.parametrize("d,rows,n_pad", [(7850, 64, 8192),
                                          (50090, 392, 50176),
                                          (1663370, 504, 1677312)])
def test_derived_geometry_of_the_benchmark_models(d, rows, n_pad):
    spec, br = flat_geometry(_params_of(d), 0)
    assert (br, spec.n_pad) == (rows, n_pad)


def test_explicit_block_rows_geometry_unchanged():
    """An explicit block_rows keeps its meaning: pad to its block, as
    flat_spec does."""
    tree = _mixed_tree()
    spec, br = flat_geometry(tree, 4)
    assert br == 4 and spec is flat_spec(tree, block=4 * LANES)
    assert spec.n_pad % 512 == 0 and 0 <= spec.n_pad - spec.d < 512
    spec, br = flat_geometry(_params_of(7850), 512)
    assert (br, spec.n_pad) == (512, 65536)
