"""Token-length distributions of the traffic files. Every seed gets
the SAME multiset of lengths — the distribution's evenly spaced
quantiles — in another order: runs with different seeds then offer the
same requests. The balance holds block by block (a closed loop's round
of clients): a window uses only part of what is drawn, and two seeds
whose windows held different parts read 3% apart on the chip while two
runs of one seed agreed to 1% (PR 26).

What a seed changes, then, is the ORDER, and what it cannot change is
the work offered. It can still change the work DONE: where a step's
cost depends on which requests meet in it (since PR 32 a mixed step
runs at the narrowest of a few widths that holds its tokens), the
order decides how many steps run wide, and a window of some thousand
steps is one sample of that. The two ``prefill-closed`` cells read 2%
to 5% apart from seed to seed for this reason alone (PR 38, PERF.md
section 2); their traffic file therefore fixes the order too
(``generators/closed.py``, the ``order`` key) and leaves the seed the
seats and the tokens. The decode cells' and the hybrid cell's steps
cost the same whoever meets in them, and their seeds draw the order.

This is for lengths in a closed loop; an open loop's arrival gaps are
not to be balanced so (its bursts are what it measures; PERF.md
section 7)."""
import numpy as np


def quantile(dist, u):
    """The ``u``-quantile (0 < u < 1) of a traffic file's length
    distribution, as a whole number of tokens inside [lo, hi]."""
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(hi, max(lo, round(x))))


GOLDEN = 0.6180339887498949


def unit_block(n, block, rng):
    """``n`` numbers in (0, 1): the grid (i + f) / n, i < n, shuffled by
    ``rng``. The offset f depends on the block's index alone (a golden-
    ratio sequence), so one block after another covers the interval
    ever more finely and every seed gets the same numbers."""
    f = ((block + 1) * GOLDEN) % 1.0
    return ((rng.permutation(n) + f) / n).tolist()


def block(dist, n, index, rng):
    """``n`` lengths, one from each of the distribution's ``n`` equal
    strata (``unit_block``), in a seeded order."""
    return [quantile(dist, u) for u in unit_block(n, index, rng)]


def tokens(rng, n, vocab):
    return rng.integers(0, vocab, int(n), dtype=np.int64).tolist()
