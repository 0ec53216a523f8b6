"""``JobResult.records`` as a read-only view over sorted host arrays
(``repro.core.records.Records``): every read agrees with ``dict(view)``
for one-lane keys at P=1 and at P=4 (four CPU devices in a child
process), two-lane keys and a sentinel-padded window; the benchmark's
``as_arrays`` reads the view as it reads the dict; the built-in
finalizers give the same output from the view as from a plain dict;
``result()`` builds no dict of the records; and co-scheduled members'
views equal their solo runs."""
import json
import os
import sys
import tracemalloc
from dataclasses import dataclass

import numpy as np
import pytest

from repro.core import (Histogram, InvertedIndex, JobConfig, JobScheduler,
                        Postings, PostingsIndex, WordCount, submit)
from repro.core.kv import KEY_SENTINEL
from repro.core.records import Records

SENT = int(KEY_SENTINEL)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, TASK = 1 << 12, 512


def check_view(view) -> int:
    """Assert that every read of ``view`` agrees with ``dict(view)``;
    returns the number of records."""
    d = dict(view)
    assert isinstance(view, Records) and not isinstance(view, dict)
    keys = list(view)
    assert keys == sorted(d) == list(view.keys())
    assert len(view) == len(d) == len(view.keys()) == len(view.items())
    assert list(view.values()) == [d[k] for k in keys]
    assert list(view.items()) == list(d.items())
    assert view.key_array.tolist() == keys
    assert view.value_array.tolist() == list(view.values())
    for k in keys[::max(1, len(keys) // 64)] + keys[-1:]:
        assert view[k] == view.get(k) == d[k]
        assert k in view and k in view.keys()
        assert (k, d[k]) in view.items()
    lo, hi = (keys[0], keys[-1]) if keys else (0, 0)
    gaps = [k + 1 for a, k in zip(keys[1:], keys) if a > k + 1][:3]
    for k in [lo - 1, hi + 1, -(1 << 40), (1 << 62), "x", None] + gaps:
        assert k not in d
        with pytest.raises(KeyError):
            view[k]
        assert k not in view and view.get(k, "none") == "none"
    assert view == d and d == view and not view != d
    assert view == Records(view.key_array, view.value_array)
    if keys:
        bumped = dict(d)
        bumped[keys[0]] += 1
        assert view != bumped and bumped != view
        fewer = dict(d)
        del fewer[keys[-1]]
        assert view != fewer and fewer != view
    assert view != dict(d, **{"x": 1}) and view != list(d.items())
    for a in (view.key_array, view.value_array):
        with pytest.raises(ValueError):
            a[:1] = 0
    return len(view)


def _run(usecase, tokens, **kw):
    cfg = JobConfig(usecase=usecase, backend="1s", task_size=TASK,
                    push_cap=64, n_procs=1, **kw)
    return submit(cfg, tokens).result()


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(0)
    t = (rng.zipf(1.2, 9 * TASK + 100) % VOCAB).astype(np.int32)
    t[[3, 700]] = SENT                       # padding inside the input
    return t


def _shuffled(n, seed):
    rng = np.random.default_rng(seed)
    keys = rng.choice(1 << 40, n, replace=False).astype(np.int64)
    return Records(keys, rng.integers(1, 1000, n))


VIEWS = {
    "one-lane": lambda t: _run(WordCount(vocab=VOCAB), t).records,
    "two-lane": lambda t: _run(PostingsIndex(vocab=VOCAB, doc_len=300),
                               t, segment=2).records,
    "unsorted-input": lambda t: _shuffled(500, 1),
    "empty": lambda t: Records(np.zeros(0, np.int32), np.zeros(0, np.int32)),
}


@pytest.mark.parametrize("case", sorted(VIEWS))
def test_view_reads_like_its_dict(case, tokens):
    n = check_view(VIEWS[case](tokens))
    assert (n == 0) == (case == "empty")


def test_sentinel_padded_window_leaves_no_sentinel(tokens):
    # 64 bins over ids that fill only the first few: the window comes back
    # mostly sentinel slots, which the view leaves out
    res = _run(Histogram(vocab=VOCAB * 16, n_bins=64), tokens)
    assert (res.keys == SENT).sum() > 32
    assert check_view(res.records) == int((res.keys != SENT).sum())
    assert SENT not in res.records


def test_view_reads_like_its_dict_at_four_devices(devices8, tokens):
    out = devices8(f"""
        import sys
        import numpy as np
        sys.path.insert(0, {os.path.join(ROOT, "tests")!r})
        from test_records_view import check_view
        from repro.core import JobConfig, WordCount, submit
        tokens = np.asarray({tokens.tolist()!r}, np.int32)
        for segment in (0, 2):
            res = submit(JobConfig(usecase=WordCount(vocab={VOCAB}),
                                   backend="1s", task_size={TASK},
                                   push_cap=64, n_procs=4, segment=segment),
                         tokens).result()
            print(check_view(res.records), dict(res.records) ==
                  {{int(k): int(v) for k, v in zip(*np.unique(
                      tokens[tokens != {SENT}], return_counts=True))}})
    """, n_devices=4)
    lines = out.strip().splitlines()[-2:]
    want = len(np.unique(tokens[tokens != SENT]))
    assert lines == [f"{want} True"] * 2


@pytest.mark.parametrize("case", ["one-lane", "two-lane", "unsorted-input"])
def test_as_arrays_reads_the_view_as_the_dict(case, tokens):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench.run import as_arrays
    view = VIEWS[case](tokens)
    got, want = as_arrays(view), as_arrays(dict(view))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)


FINALIZERS = {
    "histogram": Histogram(vocab=VOCAB, n_bins=16),
    "inverted-index": InvertedIndex(queries=(1, 2, 5, 9), n_docs=4,
                                    tasks_per_doc=3),
    "postings": PostingsIndex(vocab=VOCAB, doc_len=300),
}


def _same_output(a, b):
    if isinstance(a, tuple):                 # Postings, field by field
        assert type(a) is type(b)
        for x, y in zip(a, b):
            assert x.dtype == y.dtype and np.array_equal(x, y)
    elif isinstance(a, np.ndarray):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    else:                                    # {query: {doc: tf}}, in order
        assert [(q, list(p.items())) for q, p in a.items()] == \
            [(q, list(p.items())) for q, p in b.items()]


def _loop_finalize(uc, records):
    """The finalizers as per-record loops over the items: the reference."""
    if isinstance(uc, Histogram):
        out = np.zeros((uc.n_bins,), np.int64)
        for b, c in records.items():
            out[b] = c
        return out
    if isinstance(uc, InvertedIndex):
        out = {int(t): {} for t in uc.queries}
        for k, v in records.items():
            doc, q = divmod(int(k), len(uc.queries))
            out[int(uc.queries[q])][doc] = int(v)
        return out
    words, offsets, docs, freqs = [], [], [], []
    for k, v in sorted(records.items()):
        if not words or words[-1] != k >> 32:
            words.append(k >> 32)
            offsets.append(len(docs))
        docs.append(k & 0xFFFFFFFF)
        freqs.append(v)
    offsets.append(len(docs))
    return Postings(*(np.asarray(x, np.int64)
                      for x in (words, offsets, docs, freqs)))


@pytest.mark.parametrize("name", sorted(FINALIZERS))
def test_finalize_from_the_view_equals_from_a_dict(name, tokens):
    uc = FINALIZERS[name]
    res = _run(uc, tokens)
    assert len(res.records) > 3
    from_view = uc.finalize(res.records)
    _same_output(from_view, res.output)
    _same_output(from_view, _loop_finalize(uc, dict(res.records)))
    _same_output(from_view, uc.finalize(dict(res.records)))
    # a plain dict in any order gives the same output
    _same_output(from_view, uc.finalize(dict(reversed(
        list(res.records.items())))))


@dataclass(frozen=True)
class _BarePostings(PostingsIndex):
    """Two-lane keys and no ``finalize``: ``result()`` does nothing past
    the records."""
    finalize = None


@pytest.mark.parametrize("usecase", [WordCount(vocab=1 << 16),
                                     _BarePostings(vocab=1 << 16,
                                                   doc_len=300)],
                         ids=["one-lane", "two-lane"])
def test_result_builds_no_dict(usecase):
    """A dict of the records holds at least a 28-byte int per key and a
    24-byte entry; ``result()`` allocates less than 48 bytes a record at
    its peak (the old dict path: 118 and 127), the copies to the host
    included."""
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, 1 << 16, 1 << 17).astype(np.int32)
    cfg = JobConfig(usecase=usecase, backend="1s", task_size=4096,
                    push_cap=1024, n_procs=1, segment=8)
    submit(cfg, tokens).result()             # compile outside the window
    h = submit(cfg, tokens)
    while h.step():
        pass
    tracemalloc.start()
    try:
        res = h.result()
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert type(res.records) is Records and type(res.output) is Records
    assert len(res.records) > 40_000
    assert peak < 48 * len(res.records), peak / len(res.records)


@pytest.mark.parametrize("usecase", [WordCount(vocab=200),
                                     Histogram(vocab=200, n_bins=16)],
                         ids=["wordcount", "histogram"])
def test_coscheduled_members_views_equal_solo(usecase):
    rng = np.random.default_rng(4)
    streams = [rng.integers(0, 200, n * TASK).astype(np.int32)
               for n in (9, 5)]
    cfg = JobConfig(usecase=usecase, backend="1s", task_size=TASK,
                    push_cap=256, n_procs=1, segment=1)
    solo = [submit(cfg, s).result() for s in streams]
    sched = JobScheduler(coschedule=True)
    handles = [sched.submit(cfg, s, tenant="t", name=f"j{i}")
               for i, s in enumerate(streams)]
    sched.run_until_complete()
    assert len(sched._domains) == 1
    for h, ref in zip(handles, solo):
        got = h.result()
        check_view(got.records)
        assert got.records == ref.records
        assert json.dumps(list(got.records.items())) == \
            json.dumps(list(ref.records.items()))
        if isinstance(ref.output, np.ndarray):
            _same_output(got.output, ref.output)
        else:
            assert got.output == ref.output
