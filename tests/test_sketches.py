import jax.numpy as jnp
import numpy as np
import pytest

from deepflow_tpu.ops.cms import cms_init, cms_merge, cms_query, cms_update
from deepflow_tpu.ops.hashing import fingerprint64
from deepflow_tpu.ops.histogram import (
    LogHistSpec,
    loghist_init,
    loghist_merge,
    loghist_quantiles,
    loghist_update,
)
from deepflow_tpu.ops.hll import (
    hll_estimate,
    hll_estimate_np,
    hll_init,
    hll_merge,
    hll_update,
)
from deepflow_tpu.ops.tdigest import (
    tdigest_compress,
    tdigest_from_loghist,
    tdigest_merge,
    tdigest_quantile,
)


def _hashes(n, seed=0, lo_card=None):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, lo_card if lo_card else 2**31, size=(n, 1), dtype=np.uint32)
    hi, lo = fingerprint64(jnp.asarray(ids))
    return ids[:, 0], hi, lo


class TestHLL:
    def test_accuracy_1pct(self):
        true_card = 100_000
        ids, hi, lo = _hashes(200_000, seed=3, lo_card=true_card)
        # ~all of true_card values appear (coupon collector at 2x draws ~86%)
        expected = len(np.unique(ids))
        state = hll_init(4, precision=14)
        gids = jnp.zeros(len(ids), dtype=jnp.int32)
        state = hll_update(state, gids, hi, lo, jnp.ones(len(ids), bool))
        est = float(hll_estimate(state)[0])
        assert abs(est - expected) / expected < 0.02
        # untouched groups estimate 0
        assert float(hll_estimate(state)[1]) == 0.0

    def test_small_range_linear_counting(self):
        ids, hi, lo = _hashes(500, seed=4, lo_card=300)
        expected = len(np.unique(ids))
        state = hll_init(1, precision=12)
        state = hll_update(state, jnp.zeros(500, jnp.int32), hi, lo, jnp.ones(500, bool))
        est = float(hll_estimate(state)[0])
        assert abs(est - expected) / expected < 0.05

    def test_merge_equals_union(self):
        ids1, hi1, lo1 = _hashes(5000, seed=5, lo_card=4000)
        ids2, hi2, lo2 = _hashes(5000, seed=6, lo_card=4000)
        s1 = hll_update(hll_init(1, 12), jnp.zeros(5000, jnp.int32), hi1, lo1, jnp.ones(5000, bool))
        s2 = hll_update(hll_init(1, 12), jnp.zeros(5000, jnp.int32), hi2, lo2, jnp.ones(5000, bool))
        both = hll_update(
            hll_update(hll_init(1, 12), jnp.zeros(5000, jnp.int32), hi1, lo1, jnp.ones(5000, bool)),
            jnp.zeros(5000, jnp.int32),
            hi2,
            lo2,
            jnp.ones(5000, bool),
        )
        merged = hll_merge(s1, s2)
        np.testing.assert_array_equal(np.asarray(merged), np.asarray(both))

    def test_group_isolation(self):
        ids, hi, lo = _hashes(2000, seed=7, lo_card=1000)
        gids = jnp.asarray((np.arange(2000) % 2).astype(np.int32))
        state = hll_update(hll_init(2, 12), gids, hi, lo, jnp.ones(2000, bool))
        e = np.asarray(hll_estimate(state))
        for g in (0, 1):
            expected = len(np.unique(ids[np.arange(2000) % 2 == g]))
            assert abs(e[g] - expected) / expected < 0.06


HLL_P = 14
HLL_SIZES = (1_000, 10_000, 36_000, 41_000, 45_000, 50_000, 250_000, 1_000_000)
_PLANES: dict = {}


def _register_planes(n: int, sketches: int = 40) -> np.ndarray:
    """`sketches` seeded p = 14 register rows of `n` distinct random
    64-bit hashes each: register = low 14 bits of the low word, rho =
    leading zeros of the high word + 1, as `hll_update` takes them."""
    if n not in _PLANES:
        m = 1 << HLL_P
        rng = np.random.default_rng([n, 0x411])
        rows = np.zeros((sketches, m), np.int32)
        for row in rows:
            h = rng.integers(0, 2**64, n, dtype=np.uint64)
            hi = (h >> np.uint64(32)).astype(np.uint32)
            reg = (h & np.uint64(m - 1)).astype(np.int64)
            rho = np.where(
                hi == 0, 33, 32 - np.floor(np.log2(np.maximum(hi, 1))).astype(np.int64)
            )
            order = np.argsort(reg, kind="stable")
            r, v = reg[order], rho[order]
            starts = np.flatnonzero(np.r_[True, r[1:] != r[:-1]])
            row[r[starts]] = np.maximum.reduceat(v, starts)
        _PLANES[n] = rows
    return _PLANES[n]


def _classic_estimate(state: np.ndarray) -> np.ndarray:
    """What `hll_estimate_np` was before PR 36: raw HyperLogLog with
    linear counting up to 2.5 m, kept here as the control."""
    m = state.shape[1]
    alpha = 0.7213 / (1.0 + 1.079 / m)
    raw = alpha * m * m / np.sum(np.exp2(-state.astype(np.float64)), axis=1)
    zeros = np.sum(state == 0, axis=1).astype(np.float64)
    linear = m * np.log(m / np.maximum(zeros, 1.0))
    return np.where((raw <= 2.5 * m) & (zeros > 0), linear, raw)


class TestHLLEstimator:
    """Ertl's improved raw estimator has no bias bump at 2.5 m = 40,960
    (p = 14), where the classic estimator leaves linear counting."""

    SIGMA = 1.04 / np.sqrt(1 << HLL_P)

    @pytest.mark.parametrize("n", HLL_SIZES)
    def test_mean_and_worst_error(self, n):
        est = hll_estimate_np(_register_planes(n))
        assert abs(est.mean() / n - 1.0) <= 0.004
        assert np.abs(est / n - 1.0).max() <= 3 * self.SIGMA

    @pytest.mark.parametrize("n", HLL_SIZES)
    def test_device_and_host_forms_agree(self, n):
        planes = _register_planes(n)
        dev = np.asarray(hll_estimate(jnp.asarray(planes)))
        np.testing.assert_allclose(dev, hll_estimate_np(planes), rtol=1e-5)

    @pytest.mark.parametrize("n", (41_000, 45_000))
    def test_classic_estimator_is_biased_at_the_switch(self, n):
        est = _classic_estimate(_register_planes(n))
        assert est.mean() / n - 1.0 > 0.004

    def test_empty_and_full_rows(self):
        m = 1 << HLL_P
        planes = np.stack([np.zeros(m, np.int32), np.full(m, 33, np.int32)])
        host = hll_estimate_np(planes)
        dev = np.asarray(hll_estimate(jnp.asarray(planes)))
        assert host[0] == 0.0 and dev[0] == 0.0
        assert np.isinf(host[1]) and np.isinf(dev[1])


class TestCMS:
    def test_point_queries_upper_bound(self):
        rng = np.random.default_rng(8)
        # zipf-ish frequencies over 1000 keys
        keys = rng.zipf(1.3, size=50_000) % 1000
        ids = keys.astype(np.uint32)[:, None]
        hi, lo = fingerprint64(jnp.asarray(ids))
        state = cms_init(depth=4, width=1 << 14)
        state = cms_update(state, hi, lo, jnp.ones(len(keys), jnp.int32), jnp.ones(len(keys), bool))

        uniq = np.unique(keys)
        uh, ul = fingerprint64(jnp.asarray(uniq.astype(np.uint32)[:, None]))
        est = np.asarray(cms_query(state, uh, ul))
        true = np.array([(keys == k).sum() for k in uniq])
        assert (est >= true).all()  # CMS never underestimates
        # heavy hitters well approximated
        heavy = true > 500
        assert np.all((est[heavy] - true[heavy]) / true[heavy] < 0.05)

    def test_merge_additive(self):
        ids = np.arange(100, dtype=np.uint32)[:, None]
        hi, lo = fingerprint64(jnp.asarray(ids))
        ones = jnp.ones(100, jnp.int32)
        v = jnp.ones(100, bool)
        s1 = cms_update(cms_init(2, 1 << 10), hi, lo, ones, v)
        s2 = cms_update(cms_init(2, 1 << 10), hi, lo, ones, v)
        m = cms_merge(s1, s2)
        est = np.asarray(cms_query(m, hi, lo))
        assert (est >= 2).all()


class TestLogHist:
    SPEC = LogHistSpec(bins=1024, vmin=1.0, gamma=1.02)

    def test_quantile_rel_error(self):
        rng = np.random.default_rng(9)
        vals = rng.lognormal(mean=6.0, sigma=1.5, size=100_000).astype(np.float32)
        state = loghist_init(1, self.SPEC)
        state = loghist_update(
            state, jnp.zeros(len(vals), jnp.int32), jnp.asarray(vals), jnp.ones(len(vals), bool), self.SPEC
        )
        qs = (0.5, 0.95, 0.99)
        est = np.asarray(loghist_quantiles(state, self.SPEC, qs))[0]
        for q, e in zip(qs, est):
            true = np.quantile(vals, q)
            assert abs(e - true) / true < 0.03, (q, e, true)

    def test_merge(self):
        rng = np.random.default_rng(10)
        a = rng.uniform(1, 1000, 5000).astype(np.float32)
        b = rng.uniform(1, 1000, 5000).astype(np.float32)
        g = jnp.zeros(5000, jnp.int32)
        v = jnp.ones(5000, bool)
        sa = loghist_update(loghist_init(1, self.SPEC), g, jnp.asarray(a), v, self.SPEC)
        sb = loghist_update(loghist_init(1, self.SPEC), g, jnp.asarray(b), v, self.SPEC)
        merged = loghist_merge(sa, sb)
        est = float(np.asarray(loghist_quantiles(merged, self.SPEC, (0.5,)))[0, 0])
        true = np.quantile(np.concatenate([a, b]), 0.5)
        assert abs(est - true) / true < 0.03


class TestTDigest:
    def test_compress_and_quantile(self):
        rng = np.random.default_rng(11)
        vals = rng.gamma(2.0, 300.0, size=20_000).astype(np.float32)
        m, w = tdigest_compress(jnp.asarray(vals), jnp.ones(len(vals), jnp.float32), compression=100)
        qs = jnp.asarray([0.5, 0.9, 0.99])
        est = np.asarray(tdigest_quantile(m, w, qs))
        for q, e in zip([0.5, 0.9, 0.99], est):
            true = np.quantile(vals, q)
            assert abs(e - true) / true < 0.05, (q, e, true)

    def test_from_loghist_pipeline(self):
        spec = LogHistSpec(bins=1024, vmin=1.0, gamma=1.02)
        rng = np.random.default_rng(12)
        vals = rng.lognormal(5.0, 1.0, size=50_000).astype(np.float32)
        state = loghist_init(2, spec)
        state = loghist_update(
            state, jnp.zeros(len(vals), jnp.int32), jnp.asarray(vals), jnp.ones(len(vals), bool), spec
        )
        means, weights = tdigest_from_loghist(state, spec, compression=64)
        assert means.shape == (2, 64)
        est = float(np.asarray(tdigest_quantile(means[0], weights[0], jnp.asarray([0.99]))[0]))
        true = np.quantile(vals, 0.99)
        assert abs(est - true) / true < 0.05
        # empty group → all-zero digest
        assert float(weights[1].sum()) == 0.0

    def test_merge_two_digests(self):
        rng = np.random.default_rng(13)
        a = rng.normal(1000, 100, 10_000).astype(np.float32)
        b = rng.normal(2000, 100, 10_000).astype(np.float32)
        ma, wa = tdigest_compress(jnp.asarray(a), jnp.ones(len(a), jnp.float32), compression=100)
        mb, wb = tdigest_compress(jnp.asarray(b), jnp.ones(len(b), jnp.float32), compression=100)
        m, w = tdigest_merge(ma, wa, mb, wb, compression=100)
        est = float(np.asarray(tdigest_quantile(m, w, jnp.asarray([0.5]))[0]))
        true = np.quantile(np.concatenate([a, b]), 0.5)
        assert abs(est - true) / true < 0.05


# ---------------------------------------------------------------------------
# ISSUE 8 property pins: merge associativity/commutativity and the error
# envelopes the 1m rollup cascade will lean on. These are CONTRACTS —
# cross-shard merge-on-close and the future multi-resolution cascade
# reorder merges freely, so any order sensitivity is a correctness bug.


def _rand_cms(seed, depth=3, width=1 << 10, n=4000):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 500, size=(n, 1), dtype=np.uint32)
    hi, lo = fingerprint64(jnp.asarray(ids))
    w = jnp.asarray(rng.integers(1, 50, n), jnp.int32)
    return cms_update(cms_init(depth, width), hi, lo, w, jnp.ones(n, bool))


class TestMergeAlgebra:
    def test_cms_merge_commutes_and_associates(self):
        a, b, c = (_rand_cms(s) for s in (20, 21, 22))
        ab = cms_merge(a, b)
        np.testing.assert_array_equal(np.asarray(ab), np.asarray(cms_merge(b, a)))
        np.testing.assert_array_equal(
            np.asarray(cms_merge(ab, c)), np.asarray(cms_merge(a, cms_merge(b, c)))
        )

    def test_cms_merge_then_query_equals_query_then_sum(self):
        """CMS is linear: counters add, so a point query over the merge
        equals the sum of per-shard queries whenever the min lands on
        the same row — and is never below either side (overestimate-only
        is preserved under merge)."""
        rng = np.random.default_rng(23)
        ids = rng.integers(0, 200, size=(2000, 1), dtype=np.uint32)
        hi, lo = fingerprint64(jnp.asarray(ids))
        ones = jnp.ones(2000, jnp.int32)
        v = jnp.ones(2000, bool)
        a = cms_update(cms_init(3, 1 << 12), hi, lo, ones, v)
        b = cms_update(cms_init(3, 1 << 12), hi, lo, ones, v)
        uniq = np.unique(ids)
        uh, ul = fingerprint64(jnp.asarray(uniq[:, None]))
        qa = np.asarray(cms_query(a, uh, ul))
        qm = np.asarray(cms_query(cms_merge(a, b), uh, ul))
        true = np.array([(ids[:, 0] == k).sum() for k in uniq])
        assert (qm >= 2 * true).all()  # merged never underestimates
        assert (qm >= qa).all()
        # identical shards: the merged estimate is exactly double
        np.testing.assert_array_equal(qm, 2 * qa)

    def test_hll_merge_commutes_and_associates(self):
        def mk(seed):
            ids, hi, lo = _hashes(3000, seed=seed, lo_card=2500)
            return hll_update(
                hll_init(2, 10), jnp.zeros(3000, jnp.int32), hi, lo,
                jnp.ones(3000, bool),
            )

        a, b, c = mk(24), mk(25), mk(26)
        np.testing.assert_array_equal(
            np.asarray(hll_merge(a, b)), np.asarray(hll_merge(b, a))
        )
        np.testing.assert_array_equal(
            np.asarray(hll_merge(hll_merge(a, b), c)),
            np.asarray(hll_merge(a, hll_merge(b, c))),
        )

    def test_hll_merge_is_idempotent_union(self):
        """merge(a, a) == a — the property that makes retried/replayed
        cross-shard merges harmless."""
        ids, hi, lo = _hashes(2000, seed=27, lo_card=1000)
        a = hll_update(hll_init(1, 10), jnp.zeros(2000, jnp.int32), hi, lo,
                       jnp.ones(2000, bool))
        np.testing.assert_array_equal(np.asarray(hll_merge(a, a)), np.asarray(a))

    def test_hll_error_envelope_at_precision14(self):
        """The north-star bound: <1% relative error at p=14 with ~1M
        distinct keys (seeded draw; standard error at p=14 is ~0.81%)."""
        n = 1_000_000
        rng = np.random.default_rng(28)
        ids = rng.integers(0, 1 << 62, size=n, dtype=np.int64)
        lanes = np.stack(
            [(ids & 0xFFFFFFFF).astype(np.uint32), (ids >> 32).astype(np.uint32)],
            axis=1,
        )
        hi, lo = fingerprint64(jnp.asarray(lanes))
        state = hll_update(
            hll_init(1, 14), jnp.zeros(n, jnp.int32), hi, lo, jnp.ones(n, bool)
        )
        expected = len(np.unique(ids))
        est = float(hll_estimate(state)[0])
        assert abs(est - expected) / expected < 0.01, (est, expected)

    def test_loghist_merge_commutes_and_associates(self):
        spec = LogHistSpec(bins=128, vmin=1.0, gamma=1.1)

        def mk(seed):
            rng = np.random.default_rng(seed)
            vals = rng.uniform(1, 500, 2000).astype(np.float32)
            return loghist_update(
                loghist_init(1, spec), jnp.zeros(2000, jnp.int32),
                jnp.asarray(vals), jnp.ones(2000, bool), spec,
            )

        a, b, c = mk(29), mk(30), mk(31)
        np.testing.assert_array_equal(
            np.asarray(loghist_merge(a, b)), np.asarray(loghist_merge(b, a))
        )
        np.testing.assert_array_equal(
            np.asarray(loghist_merge(loghist_merge(a, b), c)),
            np.asarray(loghist_merge(a, loghist_merge(b, c))),
        )

    def test_tdigest_merge_commutes_and_associates_on_quantiles(self):
        """t-digest merge is associative *up to the digest's accuracy
        guarantee* — pin commutativity exactly (random float means have
        no sort ties) and associativity through the quantile surface."""
        rng = np.random.default_rng(32)

        def mk(mu):
            v = rng.normal(mu, 50, 5000).astype(np.float32)
            return tdigest_compress(
                jnp.asarray(v), jnp.ones(5000, jnp.float32), compression=64
            )

        (ma, wa), (mb, wb), (mc, wc) = mk(500), mk(1500), mk(2500)
        m_ab, w_ab = tdigest_merge(ma, wa, mb, wb)
        m_ba, w_ba = tdigest_merge(mb, wb, ma, wa)
        np.testing.assert_allclose(np.asarray(m_ab), np.asarray(m_ba), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(w_ab), np.asarray(w_ba), rtol=1e-6)
        qs = jnp.asarray([0.1, 0.5, 0.9, 0.99])
        m1, w1 = tdigest_merge(m_ab, w_ab, mc, wc)
        m_bc, w_bc = tdigest_merge(mb, wb, mc, wc)
        m2, w2 = tdigest_merge(ma, wa, m_bc, w_bc)
        q1 = np.asarray(tdigest_quantile(m1, w1, qs))
        q2 = np.asarray(tdigest_quantile(m2, w2, qs))
        np.testing.assert_allclose(q1, q2, rtol=0.05)

    def test_tdigest_merge_tracks_concat_quantiles(self):
        """merge-then-query tracks query-over-concatenation — the "sum"
        semantics for quantile sketches."""
        rng = np.random.default_rng(33)
        a = rng.gamma(2.0, 100.0, 8000).astype(np.float32)
        b = rng.gamma(3.0, 200.0, 8000).astype(np.float32)
        ma, wa = tdigest_compress(jnp.asarray(a), jnp.ones(len(a), jnp.float32), compression=100)
        mb, wb = tdigest_compress(jnp.asarray(b), jnp.ones(len(b), jnp.float32), compression=100)
        m, w = tdigest_merge(ma, wa, mb, wb, compression=100)
        both = np.concatenate([a, b])
        for q in (0.5, 0.9, 0.99):
            est = float(np.asarray(tdigest_quantile(m, w, jnp.asarray([q])))[0])
            true = np.quantile(both, q)
            assert abs(est - true) / true < 0.05, (q, est, true)


# ---------------------------------------------------------------------------
# invertible top-K sketch (ops/topk.py)

from deepflow_tpu.ops.topk import (  # noqa: E402
    topk_candidates,
    topk_init,
    topk_merge,
    topk_select,
    topk_update,
)


def _zipf_keys(n, n_keys, s, seed):
    rng = np.random.default_rng(seed)
    ranks = rng.zipf(s, size=4 * n)
    ranks = ranks[ranks <= n_keys][:n].astype(np.uint32)
    return ranks


def _key_fp(keys):
    return fingerprint64(jnp.asarray(np.asarray(keys, np.uint32)[:, None]))


class TestTopKSketch:
    def test_recovers_planted_heavy_keys(self):
        keys = _zipf_keys(30_000, 5000, 1.3, seed=40)
        hi, lo = _key_fp(keys)
        lanes = topk_init(2, 256)
        k_arr = jnp.asarray(keys)
        lanes = topk_update(
            lanes, jnp.zeros(len(keys), jnp.int32), hi, lo, k_arr, k_arr,
            jnp.ones(len(keys), jnp.int32), jnp.ones(len(keys), bool),
        )
        ch, cl, cia, _, votes = topk_candidates(*lanes)
        # inversion: candidate ids came straight from the bucket lanes
        uniq, counts = np.unique(keys, return_counts=True)
        true_top = set(uniq[np.argsort(-counts)][:10].tolist())
        recovered = set(int(x) for x in cia)
        assert len(true_top & recovered) >= 9, (true_top, recovered)

    def test_update_respects_slot_isolation(self):
        """Rows of different ring slots never touch each other's buckets."""
        keys = np.arange(100, dtype=np.uint32)
        hi, lo = _key_fp(keys)
        lanes = topk_init(1, 64, ring=2)
        slot = jnp.asarray((keys % 2).astype(np.int32))
        lanes = topk_update(
            lanes, slot, hi, lo, jnp.asarray(keys), jnp.asarray(keys),
            jnp.ones(100, jnp.int32), jnp.ones(100, bool),
        )
        ida = np.asarray(lanes[3])
        votes = np.asarray(lanes[0])
        assert (ida[0][votes[0] > 0] % 2 == 0).all()
        assert (ida[1][votes[1] > 0] % 2 == 1).all()

    def test_merge_commutes_functionally(self):
        def mk(seed):
            keys = _zipf_keys(5000, 800, 1.3, seed=seed)
            hi, lo = _key_fp(keys)
            lanes = topk_init(2, 128)
            return topk_update(
                lanes, jnp.zeros(len(keys), jnp.int32), hi, lo,
                jnp.asarray(keys), jnp.asarray(keys),
                jnp.ones(len(keys), jnp.int32), jnp.ones(len(keys), bool),
            )

        a, b = mk(41), mk(42)
        ab = topk_merge(a, b)
        ba = topk_merge(b, a)
        # votes agree exactly; surviving keys agree wherever the bucket
        # is live (an exact vote tie leaves a dead bucket either way)
        np.testing.assert_array_equal(np.asarray(ab[0]), np.asarray(ba[0]))
        live = np.asarray(ab[0]) > 0
        np.testing.assert_array_equal(
            np.asarray(ab[1])[live], np.asarray(ba[1])[live]
        )

    def test_select_ranks_by_estimate_and_dedupes(self):
        hi = np.asarray([1, 1, 2, 3], np.uint32)
        lo = np.asarray([9, 9, 8, 7], np.uint32)
        ia = np.asarray([10, 10, 20, 30], np.uint32)
        est = np.asarray([5, 5, 50, 20])
        h, l, a, b, e = topk_select(hi, lo, ia, ia, est, 2)
        assert h.tolist() == [2, 3] and e.tolist() == [50, 20]
