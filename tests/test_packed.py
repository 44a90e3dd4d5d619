"""PackedLPBatch — the canonical SoA constraint layout: lossless
conversions, packed-native batch utilities as bit-identical twins of the
AoS ones, pytree/jit behaviour, and the pack-call counter."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.packed as packed_mod
from repro.core import (LPBatch, PackedLPBatch, concat_batches,
                        concat_packed, make_batch, normalize_batch,
                        normalize_packed, pack, pack_call_count,
                        pad_batch, pad_batch_dim, pad_packed,
                        pad_packed_batch_dim, ragged_feasible_lp,
                        random_feasible_lp, shuffle_batch, shuffle_packed,
                        split_batch, split_packed, unpack)
from repro.kernels import ops

from _pad_reference import scatter_pad_packed, scatter_pad_packed_batch_dim


def _assert_batches_equal(a: LPBatch, b: LPBatch):
    np.testing.assert_array_equal(np.asarray(a.A), np.asarray(b.A))
    np.testing.assert_array_equal(np.asarray(a.b), np.asarray(b.b))
    np.testing.assert_array_equal(np.asarray(a.c), np.asarray(b.c))
    np.testing.assert_array_equal(np.asarray(a.m_valid),
                                  np.asarray(b.m_valid))


# -- conversions ---------------------------------------------------------

def test_pack_unpack_lossless():
    lp = ragged_feasible_lp(jax.random.key(0), 6, 23)
    pb = pack(lp)
    assert isinstance(pb, PackedLPBatch)
    assert pb.L.shape == (6, 4, 23)
    assert pb.c.shape == (6, 2)
    assert pb.m_valid.shape == (6, 1)
    _assert_batches_equal(unpack(pb), lp)
    # convenience methods mirror the functions
    _assert_batches_equal(lp.pack().unpack(), lp)


def test_pack_layout_rows():
    lp = random_feasible_lp(jax.random.key(1), 3, 7)
    pb = pack(lp)
    np.testing.assert_array_equal(np.asarray(pb.ax),
                                  np.asarray(lp.A[..., 0]))
    np.testing.assert_array_equal(np.asarray(pb.ay),
                                  np.asarray(lp.A[..., 1]))
    np.testing.assert_array_equal(np.asarray(pb.b), np.asarray(lp.b))
    assert np.all(np.asarray(pb.L[:, 3, :]) == 0.0)


def test_pack_with_m_pad_neutral_tail():
    lp = random_feasible_lp(jax.random.key(2), 4, 10)
    pb = pack(lp, m_pad=16)
    assert pb.m_pad == 16
    # tail columns are the neutral constraint 0*x <= 1
    assert np.all(np.asarray(pb.L[:, 0:2, 10:]) == 0.0)
    assert np.all(np.asarray(pb.L[:, 2, 10:]) == 1.0)
    # m_valid untouched: unpack keeps padding inert for the solvers
    np.testing.assert_array_equal(np.asarray(pb.m_valid[:, 0]),
                                  np.asarray(lp.m_valid))
    with pytest.raises(ValueError):
        pack(lp, m_pad=5)


# -- packed-native twins of the lp.* batch utilities ---------------------

def test_pad_packed_matches_pad_batch():
    lp = ragged_feasible_lp(jax.random.key(3), 5, 12)
    _assert_batches_equal(unpack(pad_packed(pack(lp), 20)),
                          pad_batch(lp, 20))
    with pytest.raises(ValueError):
        pad_packed(pack(lp), 4)


def test_pad_packed_batch_dim_neutral_problems():
    lp = ragged_feasible_lp(jax.random.key(4), 3, 9)
    pp = pad_packed_batch_dim(pack(lp), 8)
    assert pp.batch == 8
    _assert_batches_equal(unpack(pp), pad_batch_dim(lp, 8))
    _assert_batches_equal(split_packed(pp, [3], allow_remainder=True)[0]
                          .unpack(), lp)
    with pytest.raises(ValueError):
        pad_packed_batch_dim(pack(lp), 2)


def _signed_zero_packed(seed, batch, m):
    """A packed batch whose rows hold -0.0 beside +0.0 and random
    values, so a pad that touched an input word would show in its bits."""
    rng = np.random.default_rng(seed)
    L = rng.standard_normal((batch, 4, m)).astype(np.float32)
    L[rng.random(L.shape) < 0.25] = -0.0
    L[rng.random(L.shape) < 0.1] = 0.0
    return PackedLPBatch(
        L=jnp.asarray(L),
        c=jnp.asarray(rng.standard_normal((batch, 2)).astype(np.float32)),
        m_valid=jnp.asarray(rng.integers(0, m + 1, (batch, 1)),
                            jnp.int32))


def _assert_bits_equal(got: PackedLPBatch, want: PackedLPBatch):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        # raw bits: -0.0 and +0.0 differ here
        assert np.array_equal(g.view(np.uint8), w.view(np.uint8))


@pytest.mark.parametrize("tail", [0, 1, 7])
@pytest.mark.parametrize("lanes", [128, 256])
@pytest.mark.parametrize("m", [1, 63, 64, 100, 127, 128, 129])
def test_padding_bit_identical_to_scatter_form(m, lanes, tail):
    tile = 8
    B = 2 * tile + tail
    pb = _signed_zero_packed(1000 * m + 10 * lanes + tail, B, m)
    m_pad = -(-m // lanes) * lanes
    b_pad = -(-B // tile) * tile
    lane_padded = pad_packed(pb, m_pad)
    _assert_bits_equal(lane_padded, scatter_pad_packed(pb, m_pad))
    _assert_bits_equal(
        pad_packed_batch_dim(lane_padded, b_pad),
        scatter_pad_packed_batch_dim(lane_padded, b_pad))
    # as the solver runs it: both pads in one jitted program
    both = jax.jit(lambda p: pad_packed_batch_dim(pad_packed(p, m_pad),
                                                  b_pad))
    _assert_bits_equal(both(pb), scatter_pad_packed_batch_dim(
        scatter_pad_packed(pb, m_pad), b_pad))


def test_concat_packed_bit_identical_to_scatter_form():
    members = [_signed_zero_packed(s, b, m)
               for s, (b, m) in enumerate([(3, 5), (2, 64), (4, 130),
                                           (1, 128)])]
    want = [scatter_pad_packed(pb, 130) for pb in members]
    _assert_bits_equal(concat_packed(members), PackedLPBatch(
        L=jnp.concatenate([pb.L for pb in want]),
        c=jnp.concatenate([pb.c for pb in want]),
        m_valid=jnp.concatenate([pb.m_valid for pb in want])))


def test_concat_split_packed_roundtrip():
    b1 = ragged_feasible_lp(jax.random.key(5), 4, 10)
    b2 = ragged_feasible_lp(jax.random.key(6), 3, 25)
    fused = concat_packed([pack(b1), pack(b2)])
    assert fused.batch == 7 and fused.m_pad == 25
    _assert_batches_equal(unpack(fused), concat_batches([b1, b2]))
    back1, back2 = split_packed(fused, [4, 3])
    _assert_batches_equal(unpack(back2), pack(b2).unpack())
    assert back1.m_pad == 25
    with pytest.raises(ValueError):
        split_packed(fused, [4, 2])      # silent remainder rejected
    with pytest.raises(ValueError):
        split_packed(fused, [4, 4])      # overflow rejected
    with pytest.raises(ValueError):
        concat_packed([])


def test_normalize_packed_bit_identical_to_aos():
    lp = ragged_feasible_lp(jax.random.key(7), 6, 15)
    # scale the batch so normalisation actually does arithmetic
    lp = LPBatch(A=lp.A * 3.7, b=lp.b * 3.7, c=lp.c, m_valid=lp.m_valid)
    _assert_batches_equal(unpack(normalize_packed(pack(lp))),
                          normalize_batch(lp))


def test_shuffle_packed_bit_identical_to_aos():
    lp = ragged_feasible_lp(jax.random.key(8), 5, 17)
    key = jax.random.key(42)
    _assert_batches_equal(unpack(shuffle_packed(key, pack(lp))),
                          shuffle_batch(key, lp))


def test_split_batch_packed_matches_aos():
    lp = random_feasible_lp(jax.random.key(9), 8, 6)
    for p_aos, p_soa in zip(split_batch(lp, [5, 3]),
                            split_packed(pack(lp), [5, 3])):
        _assert_batches_equal(p_aos, unpack(p_soa))


# -- pytree / jit behaviour ----------------------------------------------

def test_packed_is_pytree():
    pb = pack(random_feasible_lp(jax.random.key(10), 4, 8))
    leaves = jax.tree_util.tree_leaves(pb)
    assert len(leaves) == 3
    # transparently traceable: jit over the dataclass
    f = jax.jit(lambda p: dataclasses.replace(p, L=p.L * 2.0))
    doubled = f(pb)
    np.testing.assert_allclose(np.asarray(doubled.L),
                               np.asarray(pb.L) * 2.0)


def test_packed_dtype_follows_batch():
    lp = make_batch(np.ones((2, 3, 2), np.float32), np.ones((2, 3)),
                    np.ones((2, 2)))
    pb = pack(lp)
    assert pb.L.dtype == jnp.float32 and pb.c.dtype == jnp.float32
    assert pb.m_valid.dtype == jnp.int32


# -- pack-call accounting ------------------------------------------------

def test_pack_call_counter():
    lp = random_feasible_lp(jax.random.key(11), 2, 5)
    n0 = pack_call_count()
    pack(lp)
    assert pack_call_count() == n0 + 1
    ops.pack_constraints(lp)             # compat wrapper counts too
    assert pack_call_count() == n0 + 2
    # packed-native ops never repack
    pb = pack(lp)
    n1 = pack_call_count()
    normalize_packed(shuffle_packed(jax.random.key(0), pad_packed(pb, 8)))
    unpack(pb)
    assert pack_call_count() == n1
    assert packed_mod.pack_call_count() == n1
