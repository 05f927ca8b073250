"""Consensus mixing invariants: the operator w = Pi x (paper eq. 5/6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypcompat import given, settings, st, hnp

from repro.core.consensus import (
    FactoredMix,
    consensus_error_pytree,
    consensus_error_stacked,
    mix_pytree_list,
    mix_pytree_stacked,
    mix_stacked,
)
from repro.core.topology import make_topology


@given(
    x=hnp.arrays(np.float32, (5, 7), elements=st.floats(-10, 10, width=32)),
)
@settings(max_examples=30, deadline=None)
def test_mixing_preserves_mean(x):
    """1^T Pi = 1^T  =>  the agent-average is invariant under mixing."""
    t = make_topology("ring", 5)
    mixed = mix_stacked(jnp.asarray(t.pi), jnp.asarray(x))
    np.testing.assert_allclose(np.mean(np.asarray(mixed), 0), x.mean(0),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", ["ring", "chain", "torus", "fully_connected"])
def test_mixing_contracts_consensus_error(name):
    """||x - mean|| shrinks by at least lambda_2 per mixing round."""
    t = make_topology(name, 8)
    x = jnp.asarray(np.random.randn(8, 33).astype(np.float32))
    e0 = float(consensus_error_stacked(x))
    x1 = mix_stacked(jnp.asarray(t.pi), x)
    e1 = float(consensus_error_stacked(x1))
    assert e1 <= t.lambda2 * e0 + 1e-5


def test_stacked_and_list_mixing_agree():
    t = make_topology("erdos_renyi", 6, seed=3)
    trees = [{"a": jnp.asarray(np.random.randn(3, 4).astype(np.float32)),
              "b": jnp.asarray(np.random.randn(2).astype(np.float32))}
             for _ in range(6)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    mixed_stacked = mix_pytree_stacked(jnp.asarray(t.pi), stacked)
    mixed_list = mix_pytree_list(t.pi, trees)
    for j in range(6):
        np.testing.assert_allclose(np.asarray(mixed_stacked["a"][j]),
                                   np.asarray(mixed_list[j]["a"]), rtol=2e-5, atol=2e-5)


def test_uniform_mixing_reaches_exact_consensus_in_one_round():
    t = make_topology("fully_connected", 5)
    x = jnp.asarray(np.random.randn(5, 9).astype(np.float32))
    x1 = mix_stacked(jnp.asarray(t.pi), x)
    assert float(consensus_error_stacked(x1)) < 1e-5


def test_factored_mix_equals_kron_dense():
    """Sequential per-axis mixing == Kronecker-product Pi (DESIGN.md §5)."""
    ta = make_topology("ring", 4)
    tb = make_topology("fully_connected", 2)
    fm = FactoredMix((("a", ta), ("b", tb)))
    pi = fm.dense_pi()
    assert pi.shape == (8, 8)
    assert np.allclose(pi.sum(0), 1) and np.allclose(pi.sum(1), 1)
    assert fm.lambda2 == pytest.approx(ta.lambda2)
    x = np.random.randn(8, 5).astype(np.float32)
    want = pi @ x
    # emulate sequential mixing on the reshaped (4, 2, 5) tensor
    xr = x.reshape(4, 2, 5)
    step1 = np.einsum("jl,lbe->jbe", ta.pi, xr)          # mix over axis a
    step2 = np.einsum("km,jme->jke", tb.pi, step1)       # mix over axis b
    np.testing.assert_allclose(step2.reshape(8, 5), want, rtol=1e-5, atol=1e-5)


def test_consensus_error_pytree_zero_at_consensus():
    x = jnp.ones((4, 3))
    tree = {"w": x, "b": 2 * x}
    assert float(consensus_error_pytree(tree)) == pytest.approx(0.0, abs=1e-6)


# per-agent leaf shapes of rank 0 to 3: a scalar, vectors, a matrix and a
# stacked-layer leaf (rank 4 once the agent axis leads)
_LEAF_SHAPES = {"s": (), "v": (7,), "b": (33,), "w": (3, 5), "l": (2, 3, 4)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("agents", [2, 4, 16])
def test_consensus_error_pytree_matches_float64_reference(agents, dtype):
    """``mean_j sqrt(sum_leaves ||x_j - mean||^2)`` against NumPy float64 on
    the same (rounded) values: the metric is computed in float32 whatever
    the leaves' dtype."""
    rng = np.random.default_rng(agents)
    tree = {k: jnp.asarray(rng.normal(size=(agents,) + s), dtype)
            for k, s in _LEAF_SHAPES.items()}
    sq = np.zeros(agents)
    for x in jax.tree.leaves(tree):
        x64 = np.asarray(x.astype(jnp.float32), np.float64)
        d = (x64 - x64.mean(axis=0)).reshape(agents, -1)
        sq += np.sum(d * d, axis=1)
    want = np.mean(np.sqrt(sq))
    got = consensus_error_pytree(tree)
    assert got.dtype == jnp.float32
    assert float(got) == pytest.approx(want, rel=1e-5)
