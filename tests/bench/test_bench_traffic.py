"""The traffic generator: deterministic per seed, with the stated laws."""
import json

import numpy as np
import pytest

from bench import cells, generate


def _mix(name):
    with open(cells.BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)["keys"]


ZIPF = _mix("zipf-wiki")
RATINGS = _mix("ratings-netflix")
SHARES = np.asarray(RATINGS["shares"], np.float64) / sum(RATINGS["shares"])


def test_zipf_bounds_rise_and_keep_every_id_reachable():
    b = generate.cdf_bounds(ZIPF, 1 << 22)
    assert b.dtype == np.uint32 and len(b) == (1 << 22) - 1
    widths = np.diff(b.astype(np.int64))
    assert widths.min() >= 1                    # the tail stays reachable
    assert b[0] > 0


def test_shares_bounds_follow_the_shares():
    b = generate.cdf_bounds(RATINGS, 5)
    np.testing.assert_allclose(b / 2.0 ** 32, np.cumsum(SHARES)[:-1],
                               atol=1e-9)
    # counts and the shares they give are one law
    same = generate.cdf_bounds({"law": "shares", "shares": list(SHARES)}, 5)
    assert (np.abs(b.astype(np.int64) - same.astype(np.int64)) <= 1).all()


@pytest.mark.parametrize("keys", [
    {"law": "shares", "shares": [0.5, 0.5]},          # wrong length
    {"law": "shares", "shares": [0.5, 0.0, 0.5]},     # an unreachable id
    {"law": "uniform"},                               # unknown law
])
def test_bad_laws_are_refused(keys):
    with pytest.raises(ValueError):
        generate.cdf_bounds(keys, 3)


def _tokens(keys, vocab, n, seed):
    return generate.make_tokens(keys, vocab, n, seed)


def test_same_seed_same_tokens_other_seed_others():
    a = _tokens(ZIPF, 1 << 10, 1 << 15, 2 ** 31 + 7)
    b = _tokens(ZIPF, 1 << 10, 1 << 15, 2 ** 31 + 7)
    c = _tokens(ZIPF, 1 << 10, 1 << 15, 2 ** 31 + 8)
    d = _tokens(ZIPF, 1 << 10, 1 << 15, 2 ** 31 + 7 + 2 ** 32)
    assert a.dtype == np.int32 and a.shape == (1 << 15,)
    np.testing.assert_array_equal(a, b)
    assert (a != c).mean() > 0.5
    assert (a != d).mean() > 0.5                # the high word counts too


def test_a_shorter_run_draws_a_prefix_of_the_longer():
    a = _tokens(ZIPF, 1 << 10, 1 << 12, 99)
    b = _tokens(ZIPF, 1 << 10, 1 << 15, 99)
    np.testing.assert_array_equal(a, b[: 1 << 12])


def test_zipf_tokens_follow_the_truncated_law():
    vocab, n = 1 << 10, 1 << 20
    t = _tokens(ZIPF, vocab, n, 5)
    assert t.min() >= 0 and t.max() < vocab
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -ZIPF["exponent"]
    p /= p.sum()
    freq = np.bincount(t, minlength=vocab) / n
    # head ranks within 2% of the law; the tail's mass within 2% too
    np.testing.assert_allclose(freq[:8], p[:8], rtol=0.02)
    assert abs(freq[512:].sum() / p[512:].sum() - 1) < 0.02


def test_ratings_follow_their_shares():
    t = _tokens(RATINGS, 5, 1 << 18, 11)
    freq = np.bincount(t, minlength=5) / len(t)
    np.testing.assert_allclose(freq, SHARES, atol=0.003)
