"""Vectorized 64-bit fingerprints built from uint32 lanes.

TPUs have no native 64-bit integer path worth using, so the group-by key is
a pair of u32 lanes produced by two murmur3-style column folds with
different seeds. This replaces the reference's hand-packed 128-bit
`fast_id` (collector.rs:196-330): instead of packing bit-fields per Code
combination, we fingerprint *all* tag columns (inactive ones zeroed per
Code by the fanout stage), which reproduces StashKey equality with a
2^-64 collision probability per pair.

The same function serves device (jnp) and oracle (np) callers — both
array namespaces implement wrapping uint32 arithmetic.
"""

from __future__ import annotations

import jax.numpy as jnp

_C1 = 0xCC9E2D51
_C2 = 0x1B873593
_FMIX1 = 0x85EBCA6B
_FMIX2 = 0xC2B2AE35

SEED_HI = 0x9747B28C
SEED_LO = 0x3C6EF372


def _u32(x, xp):
    return xp.asarray(x, dtype=xp.uint32)


def _rotl(x, r: int, xp):
    return (x << xp.uint32(r)) | (x >> xp.uint32(32 - r))


def fmix32(h, xp=jnp):
    """murmur3 32-bit finalizer (avalanche)."""
    h = _u32(h, xp)
    h = h ^ (h >> xp.uint32(16))
    h = h * xp.uint32(_FMIX1)
    h = h ^ (h >> xp.uint32(13))
    h = h * xp.uint32(_FMIX2)
    h = h ^ (h >> xp.uint32(16))
    return h


def _fold(cols, seed: int, xp):
    """murmur3_32 body over a list of [N] u32 columns."""
    n = len(cols)
    h = None
    for c in cols:
        k = _u32(c, xp) * xp.uint32(_C1)
        k = _rotl(k, 15, xp)
        k = k * xp.uint32(_C2)
        if h is None:
            h = xp.full_like(k, xp.uint32(seed))
        h = h ^ k
        h = _rotl(h, 13, xp)
        h = h * xp.uint32(5) + xp.uint32(0xE6546B64)
    h = h ^ xp.uint32(n * 4)
    return fmix32(h, xp)


def fingerprint64(tags, xp=jnp):
    """[N, T] u32 tag matrix → (hi, lo) pair of [N] u32 fingerprints.

    Unrolled over the (static) column count; each step is a handful of VPU
    ops on [N] vectors. Device callers on the hot path use
    `fingerprint64_words` on column-major words — extracting columns from
    a row-major [N, T] device array is a strided gather on TPU (~100x the
    cost of the hash itself).
    """
    tags = xp.asarray(tags, dtype=xp.uint32)
    cols = [tags[:, j] for j in range(tags.shape[1])]
    return _fold(cols, SEED_HI, xp), _fold(cols, SEED_LO, xp)


def fingerprint64_words(words, xp=jnp):
    """Fold a pre-packed word list (datamodel.code.pack_tag_words) into
    the (hi, lo) pair. The packed representation covers the same key
    bits in ~40% fewer fold rounds than the raw column fold — the hot
    paths build the words ONCE and feed both seeds.
    Hash VALUES differ from fingerprint64 on the raw columns; only
    within-path consistency matters (every producer of a given key
    space goes through the same packing plan)."""
    return _fold(words, SEED_HI, xp), _fold(words, SEED_LO, xp)
