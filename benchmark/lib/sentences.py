"""The seeded sentence stream of the bucketed language-model cells.

Every seed gets the SAME multiset of sentence lengths (deterministic
quantiles of a log-normal clipped to the buckets), in another order and
with other token ids: a seed must not change the amount of work. Each
bucket holds a whole number of batches, so one pass of
``BucketSentenceIter`` drops nothing and always retires the same tokens.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def bucket_batches(buckets, mean, std, batches):
    """Batches per bucket, by the log-normal's share of each bucket
    (largest remainders; every bucket keeps at least one)."""
    s2 = math.log(1.0 + (std / mean) ** 2)
    mu, sigma = math.log(mean) - s2 / 2.0, math.sqrt(s2)
    cdf = [NormalDist(mu, sigma).cdf(math.log(b + 0.5)) for b in buckets]
    cdf[-1] = 1.0  # lengths are clipped into the last bucket
    share = [c - p for c, p in zip(cdf, [0.0] + cdf[:-1])]
    want = [s * batches for s in share]
    got = [max(1, int(x)) for x in want]
    order = sorted(range(len(buckets)), key=lambda i: want[i] - int(want[i]),
                   reverse=True)
    i = 0
    while sum(got) < batches:
        got[order[i % len(order)]] += 1
        i += 1
    while sum(got) > batches:
        j = max(range(len(got)), key=lambda k: got[k])
        got[j] -= 1
    return got, (mu, sigma)


def lengths(buckets, mean, std, batches, batch_size):
    """Sentence lengths, bucket by bucket: within a bucket the conditional
    quantiles of the clipped log-normal."""
    per_bucket, (mu, sigma) = bucket_batches(buckets, mean, std, batches)
    dist = NormalDist(mu, sigma)
    out, lo = [], 0
    for b, n_batches in zip(buckets, per_bucket):
        n = n_batches * batch_size
        p_lo = dist.cdf(math.log(lo + 0.5)) if lo else 0.0
        p_hi = 1.0 if b == buckets[-1] else dist.cdf(math.log(b + 0.5))
        for i in range(n):
            p = p_lo + (p_hi - p_lo) * (i + 0.5) / n
            length = int(round(math.exp(dist.inv_cdf(min(max(p, 1e-9),
                                                         1 - 1e-9)))))
            out.append(min(max(length, lo + 1), b))
        lo = b
    return out


def make(seed, *, buckets, mean, std, batches, batch_size, vocab_size,
         zipf_a):
    """[[token id, ...], ...] in a seeded order; ids 1..vocab_size-1 by a
    Zipf law (0 is the pad)."""
    rs = np.random.RandomState(int(seed) & 0xFFFFFFFF)
    lens = np.array(lengths(buckets, mean, std, batches, batch_size))
    rs.shuffle(lens)
    ranks = np.arange(1, vocab_size)
    p = ranks ** -float(zipf_a)
    p /= p.sum()
    ids = rs.choice(ranks, size=int(lens.sum()), p=p)
    cuts = np.cumsum(lens)[:-1]
    return [s.tolist() for s in np.split(ids, cuts)]
