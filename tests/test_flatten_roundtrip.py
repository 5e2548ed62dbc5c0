"""Property-style round-trip: ``flatten_to_u32 -> unflatten_from_u32`` is
the identity over mixed-dtype pytrees (bool, bf16, f32, i64), for any
padding multiple — the invariant the ``lockstep_pallas`` fused vote relies
on to reconstruct the voted state bit-for-bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (dev extra)")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops

DTYPES = ("bool", "bfloat16", "float32", "int64")


def _leaf(rng: np.random.Generator, dtype: str, shape: tuple[int, ...]):
    """Random bits of the requested dtype (NaNs and denormals included —
    the round-trip is a bitcast, not a value conversion)."""
    if dtype == "bool":
        return jnp.asarray(rng.integers(0, 2, shape).astype(np.bool_))
    nbits = jnp.dtype(dtype).itemsize * 8
    bits = rng.integers(0, 2**nbits, shape,
                        dtype=np.uint64).astype(f"uint{nbits}")
    return jax.lax.bitcast_convert_type(jnp.asarray(bits), jnp.dtype(dtype))


@settings(max_examples=25, deadline=None)
@given(
    dtypes=st.lists(st.sampled_from(DTYPES), min_size=1, max_size=5),
    shapes=st.lists(
        st.lists(st.integers(1, 5), min_size=0, max_size=3),
        min_size=5, max_size=5),
    multiple=st.sampled_from([1, 8, 128]),
    seed=st.integers(0, 2**31 - 1),
)
def test_flatten_unflatten_roundtrip(dtypes, shapes, multiple, seed):
    rng = np.random.default_rng(seed)
    with jax.enable_x64(True):  # i64 leaves survive only with x64 enabled
        tree = {
            f"leaf{i}": _leaf(rng, dt, tuple(shapes[i]))
            for i, dt in enumerate(dtypes)
        }
        layout = ops.word_layout(tree)
        flat = ops.flatten_to_u32(tree, multiple=multiple, layout=layout)
        assert flat.dtype == jnp.uint32
        assert flat.shape == (layout.padded(multiple),)
        back = ops.unflatten_from_u32(flat, tree, layout=layout)
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.dtype == b.dtype and a.shape == b.shape
            # bit-exact: compare the raw bit patterns, NaN-safe
            from repro.core.fault import bitcast_uint
            np.testing.assert_array_equal(np.asarray(bitcast_uint(a)),
                                          np.asarray(bitcast_uint(b)))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), flips=st.integers(0, 3))
def test_vote_through_packed_stream_is_elementwise_vote(seed, flips):
    """Word-granular majority voting through the packed stream equals
    elementwise majority voting on the unpacked pytree — sub-word packing
    never mixes bits across replicas."""
    from repro.core.redundancy import majority_vote
    from repro.kernels.fused_step import tmr_step

    rng = np.random.default_rng(seed)
    tree = {
        "f": _leaf(rng, "float32", (4, 3)),
        "h": _leaf(rng, "bfloat16", (5,)),
        "m": _leaf(rng, "bool", (7,)),
    }
    corrupt = jax.tree.map(jnp.array, tree)
    if flips:
        corrupt["f"] = corrupt["f"].at[0, 0].set(jnp.float32(flips))
    layout = ops.word_layout(tree)
    flats = [ops.flatten_to_u32(t, multiple=128, layout=layout)
             for t in (tree, tree, corrupt)]
    voted, _, _ = tmr_step(*flats, block=128, interpret=True)
    back = ops.unflatten_from_u32(voted, tree, layout=layout)
    want = majority_vote(tree, tree, corrupt)
    from repro.core.fault import bitcast_uint
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(bitcast_uint(a)),
                                      np.asarray(bitcast_uint(b)))
