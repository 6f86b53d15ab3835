import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jumbo_mae_tpu_tpu.ops import (
    index_sequence,
    random_masking,
    unshuffle_with_mask_tokens,
)


@pytest.mark.parametrize("mode", ["shared", "per_sample"])
def test_masking_shapes_and_mask_count(mode):
    x = jnp.arange(4 * 16 * 8, dtype=jnp.float32).reshape(4, 16, 8)
    kept, mask, ids_restore = random_masking(
        x, jax.random.key(0), keep_len=4, mode=mode
    )
    assert kept.shape == (4, 4, 8)
    assert mask.shape == (4, 16)
    # exactly length-keep_len masked positions per sample
    np.testing.assert_array_equal(np.asarray(mask.sum(-1)), np.full(4, 12.0))


def test_shared_mode_same_permutation_across_batch():
    x = jnp.broadcast_to(jnp.arange(16.0)[None, :, None], (3, 16, 2))
    kept, mask, ids_restore = random_masking(x, jax.random.key(1), 5, mode="shared")
    assert ids_restore.ndim == 1
    # every batch row kept the same token ids
    np.testing.assert_array_equal(np.asarray(kept[0]), np.asarray(kept[1]))
    np.testing.assert_array_equal(np.asarray(mask[0]), np.asarray(mask[2]))


def test_per_sample_mode_differs_across_batch():
    x = jnp.broadcast_to(jnp.arange(64.0)[None, :, None], (8, 64, 2))
    kept, mask, _ = random_masking(x, jax.random.key(2), 16, mode="per_sample")
    assert not np.array_equal(np.asarray(mask[0]), np.asarray(mask[1]))


@pytest.mark.parametrize("mode", ["shared", "per_sample"])
def test_mask_marks_exactly_the_dropped_tokens(mode):
    # token value == token index, so membership is checkable
    x = jnp.broadcast_to(jnp.arange(32.0)[None, :, None], (2, 32, 1))
    kept, mask, _ = random_masking(x, jax.random.key(3), 9, mode=mode)
    for b in range(2):
        kept_ids = set(np.asarray(kept[b, :, 0]).astype(int).tolist())
        unmasked_ids = set(np.flatnonzero(np.asarray(mask[b]) == 0.0).tolist())
        assert kept_ids == unmasked_ids


@pytest.mark.parametrize("mode", ["shared", "per_sample"])
def test_unshuffle_round_trip(mode):
    """unshuffle(kept, mask_token) restores kept tokens at their original
    positions and the mask token everywhere else."""
    x = jax.random.normal(jax.random.key(4), (2, 20, 3))
    kept, mask, ids_restore = random_masking(x, jax.random.key(5), 7, mode=mode)
    token = jnp.full((1, 1, 3), -100.0)
    full = unshuffle_with_mask_tokens(kept, token, ids_restore)
    assert full.shape == x.shape
    restored = np.asarray(full)
    orig = np.asarray(x)
    m = np.asarray(mask)
    for b in range(2):
        np.testing.assert_allclose(restored[b][m[b] == 0], orig[b][m[b] == 0])
        assert (restored[b][m[b] == 1] == -100.0).all()


def test_masking_deterministic_given_key():
    x = jax.random.normal(jax.random.key(6), (2, 50, 4))
    a = random_masking(x, jax.random.key(7), 12)
    b = random_masking(x, jax.random.key(7), 12)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


def test_index_sequence_1d_and_2d():
    x = jnp.arange(2 * 5 * 3, dtype=jnp.float32).reshape(2, 5, 3)
    ids1 = jnp.array([4, 0, 2])
    out1 = index_sequence(x, ids1)
    np.testing.assert_array_equal(np.asarray(out1[0, 0]), np.asarray(x[0, 4]))
    ids2 = jnp.array([[1, 3], [0, 2]])
    out2 = index_sequence(x, ids2)
    np.testing.assert_array_equal(np.asarray(out2[1, 1]), np.asarray(x[1, 2]))


def test_mask_algebra():
    """Parity: the m3ae mask helpers (/root/reference/src/utils_mae.py:24-49)."""
    from jumbo_mae_tpu_tpu.ops import (
        all_mask,
        mask_intersection,
        mask_not,
        mask_select,
        mask_union,
        no_mask,
    )

    x = jnp.zeros((2, 5, 3))
    z, o = no_mask(x), all_mask(x)
    np.testing.assert_array_equal(np.asarray(z), np.zeros((2, 5)))
    np.testing.assert_array_equal(np.asarray(o), np.ones((2, 5)))

    a = jnp.array([[0.0, 1.0, 0.0, 1.0, 0.0]])
    b = jnp.array([[0.0, 0.0, 1.0, 1.0, 0.0]])
    np.testing.assert_array_equal(
        np.asarray(mask_union(a, b)), [[0, 1, 1, 1, 0]]
    )
    np.testing.assert_array_equal(
        np.asarray(mask_intersection(a, b)), [[0, 0, 0, 1, 0]]
    )
    np.testing.assert_array_equal(np.asarray(mask_not(a)), [[1, 0, 1, 0, 1]])
    # de Morgan: not(a ∪ b) == not(a) ∩ not(b)
    np.testing.assert_array_equal(
        np.asarray(mask_not(mask_union(a, b))),
        np.asarray(mask_intersection(mask_not(a), mask_not(b))),
    )

    # reference argument order: second arg is the UNMASKED value
    when_unmasked = jnp.zeros((1, 5, 2))
    when_masked = jnp.full((1, 5, 2), 9.0)
    sel = mask_select(a, when_unmasked, when_masked)
    np.testing.assert_array_equal(np.asarray(sel[0, :, 0]), [0, 9, 0, 9, 0])

    # soft/weighted masks binarize like the reference ((>0) semantics)
    np.testing.assert_array_equal(
        np.asarray(mask_union(jnp.array([[0.3, 0.0]]), jnp.array([[0.2, 0.0]]))),
        [[1.0, 0.0]],
    )
    np.testing.assert_array_equal(
        np.asarray(
            mask_intersection(jnp.array([[2.0, 0.5]]), jnp.array([[0.5, 0.0]]))
        ),
        [[1.0, 0.0]],
    )
    # ...but mask_not is pure 1-x (reference semantics): 0.3 inverts to 0.7
    np.testing.assert_allclose(
        np.asarray(mask_not(jnp.array([[0.3, 0.0]]))), [[0.7, 1.0]], rtol=1e-6
    )
