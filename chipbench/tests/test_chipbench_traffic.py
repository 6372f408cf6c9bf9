"""The traffic generator: the same seed repeats exactly, every row differs,
and the rows follow the mix's language."""
import numpy as np

from bench import workload

BIG = 2 ** 31 + 12345        # wider than 32 signed bits


def test_train_batches_repeat_and_rows_all_differ():
    a = workload.train_batches(BIG, vocab=1000, batch=4, seq=32, n=3,
                               temperature=0.3)
    b = workload.train_batches(BIG, vocab=1000, batch=4, seq=32, n=3,
                               temperature=0.3)
    for x, y in zip(a, b):
        assert all(np.array_equal(x[k], y[k]) for k in x)
    rows = np.concatenate([x["tokens"] for x in a])
    assert len({tuple(r) for r in rows}) == len(rows)
    x = a[0]
    assert np.array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    assert x["tokens"].dtype == np.int32 and x["loss_mask"].min() == 1.0


def test_train_batches_follow_the_bigram_rule_where_not_noised():
    x = workload.train_batches(3, vocab=997, batch=8, seq=256, n=1,
                               temperature=0.3)[0]
    t, lab = x["tokens"].astype(np.int64), x["labels"].astype(np.int64)
    # the most common (next - a * prev) residue is the rule's b, for the a
    # that makes it most common: check that about 70 % of steps follow one
    # affine map
    best = 0
    for a in range(1, 997, 2):
        res = (lab - a * t) % 997
        best = max(best, np.bincount(res.ravel()).max() / res.size)
        if best > 0.6:
            break
    assert 0.6 < best < 0.8
