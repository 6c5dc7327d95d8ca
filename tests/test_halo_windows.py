"""Shard-count invariance of the s-step halo windows (DESIGN.md §10).

The distributed s-step and Chebyshev drivers build their matrix-powers
windows by calling ``sstep_extend_field`` / ``sstep_extend_zfactor`` on a
*shard-local* grid with the neighbour shards' edge slabs as ``below`` /
``above`` ghosts.  The §10 correctness argument rests on these windows
being identical to the single-device ones for any shard count — block i's
window holds the same slabs whether its padding was gathered locally or
exchanged from a neighbour, with zeros (fields) / ones (z-factors) at the
global domain ends either way.  This test builds the ghosts in plain
numpy, splits over 1/2/4 z-shards, and requires bitwise equality of the
stacked per-shard windows against the global windows.
"""
import numpy as np
import pytest

from repro.kernels.nekbone_ax import sstep_extend_field, sstep_extend_zfactor

EX, EY, EZ, N3 = 2, 3, 8, 5
CASES = [(1, 1), (2, 2), (1, 2)]          # (sz, halo); halo <= min ez_local


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("sz,halo", CASES)
def test_extend_field_shard_invariant(shards, sz, halo):
    rng = np.random.default_rng(11)
    eyex = EY * EX
    # kernel layout: elements (slab-major) on the last axis
    f = rng.normal(size=(N3, EZ, eyex)).astype(np.float32)
    want = np.asarray(sstep_extend_field(
        f.reshape(N3, EZ * eyex), (EX, EY, EZ), sz, halo))

    ez_l = EZ // shards
    # ghosts from the zero-padded global field: shard k's below/above are
    # the neighbour's edge slabs, exact zeros past the domain ends (the
    # padding gs.halo_exchange_z delivers there).
    pad = np.zeros((N3, halo, eyex), f.dtype)
    fp = np.concatenate([pad, f, pad], axis=1)

    def ghost(a, b):
        return fp[:, a:b].reshape(N3, halo * eyex)

    got = np.concatenate([
        np.asarray(sstep_extend_field(
            f[:, k * ez_l:(k + 1) * ez_l].reshape(N3, ez_l * eyex),
            (EX, EY, ez_l), sz, halo,
            below=ghost(k * ez_l, k * ez_l + halo),
            above=ghost((k + 1) * ez_l + halo, (k + 1) * ez_l + 2 * halo)))
        for k in range(shards)], axis=-1)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("sz,halo", CASES)
def test_extend_zfactor_shard_invariant(shards, sz, halo):
    rng = np.random.default_rng(12)
    n = 4
    fz = rng.normal(size=(EZ, n)).astype(np.float32)
    want = np.asarray(sstep_extend_zfactor(fz, sz, halo))

    ez_l = EZ // shards
    fp = np.concatenate([np.ones((halo, n), fz.dtype), fz,
                         np.ones((halo, n), fz.dtype)])  # inert ones pad
    got = np.concatenate([
        np.asarray(sstep_extend_zfactor(
            fz[k * ez_l:(k + 1) * ez_l], sz, halo,
            below=fp[k * ez_l:k * ez_l + halo],
            above=fp[(k + 1) * ez_l + halo:(k + 1) * ez_l + 2 * halo]))
        for k in range(shards)])
    assert np.array_equal(got, want)


def test_extend_field_default_pad_matches_explicit_zeros():
    """``below=None`` at the global ends == explicit zero ghosts: the two
    forms the end shards may use are interchangeable."""
    rng = np.random.default_rng(13)
    eyex = EY * EX
    f2 = rng.normal(size=(N3, EZ * eyex)).astype(np.float32)
    z = np.zeros((N3, 2 * eyex), np.float32)
    a = np.asarray(sstep_extend_field(f2, (EX, EY, EZ), 2, 2))
    b = np.asarray(sstep_extend_field(f2, (EX, EY, EZ), 2, 2,
                                      below=z, above=z))
    assert np.array_equal(a, b)

    fz = rng.normal(size=(EZ, 4)).astype(np.float32)
    one = np.ones((2, 4), np.float32)
    za = np.asarray(sstep_extend_zfactor(fz, 2, 2))
    zb = np.asarray(sstep_extend_zfactor(fz, 2, 2, below=one, above=one))
    assert np.array_equal(za, zb)
