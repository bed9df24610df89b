"""Seeded open-loop traffic: one general generator that reads a cell's
traffic parameters.

Every seed gets the same set of arrival gaps and of (prompt length, output
length) pairs, in another order, so that the amount of work in a window
does not swing with the seed; the token ids come from the seed. The set is
fixed by the traffic's own ``set_seed``, its rate and the window's length.

Serving parameters (``traffic`` in the cell's file):
  rate_per_s, arrivals ("poisson"), prompt_len and output_len as
  {"dist": "lognormal", "median", "sigma", "min", "max"}, max_total
  (prompt + output), set_seed, backlog_at_open (requests due at t = 0).
"""
from __future__ import annotations

import collections
import math

import numpy as np

Arrival = collections.namedtuple("Arrival", "due_s prompt max_new")


def _lengths(rng, spec, n):
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def schedule(traffic, seed, seconds, vocab):
    """The requests due in ``[0, seconds)``, in order of their due time."""
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    fixed = np.random.default_rng(
        [int(traffic["set_seed"]), n, int(round(seconds * 1000))])
    gaps = fixed.exponential(1.0, n)
    gaps *= seconds * n / (n + 1) / gaps.sum()  # the last one is inside
    # a cell above capacity opens on a backlog, so that the whole window is
    # saturated: these requests are due at the instant the window opens
    n_open = int(traffic.get("backlog_at_open", 0))
    gaps = np.concatenate([np.zeros(n_open), gaps])
    n += n_open
    prompts = _lengths(fixed, traffic["prompt_len"], n)
    outputs = _lengths(fixed, traffic["output_len"], n)
    outputs = np.minimum(outputs, traffic["max_total"] - prompts)
    rng = np.random.default_rng([int(seed), 1])
    due = np.cumsum(np.concatenate([gaps[:n_open],
                                    rng.permutation(gaps[n_open:])]))
    order = rng.permutation(n)
    return [Arrival(float(t), rng.integers(0, vocab, int(prompts[i])).tolist(),
                    int(outputs[i]))
            for t, i in zip(due, order)]


def train_batches(seed, vocab, batch, seq):
    """Endless seeded batches of random tokens with next-token labels; every
    row of every batch differs. Fresh host arrays: the step donates them."""
    rng = np.random.default_rng([int(seed), 2])
    while True:
        a = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
        yield a[:, :-1].copy(), a[:, 1:].copy()


def lateness_ms(due_s, sent_s):
    """How late the generator ran: (mean, max) of sent - due, in ms."""
    late = [max(0.0, s - d) * 1e3 for d, s in zip(due_s, sent_s)]
    return (sum(late) / len(late), max(late)) if late else (0.0, 0.0)
