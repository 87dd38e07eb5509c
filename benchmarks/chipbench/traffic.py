"""Seeded traffic: the schedules served from a cell's stream (the stream
itself is its kind's, ``streams/<kind>.py``).

The schedules give every seed the same amount of work in another order:
a replay pass is a fixed number of tenants each replaying a fixed-length
run of the stream, and an open-loop window fixes each tenant's number of
arrivals (its Zipf share of the aggregate rate times the window) and
draws their times uniformly over the window, which is a Poisson process
conditioned on its count.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, *purpose: int) -> np.random.Generator:
    """An independent generator for one use of the run's seed."""
    return np.random.default_rng(np.random.SeedSequence([seed, *purpose]))


def replay_offsets(rng: np.random.Generator, n_snaps: int, tenants: int,
                   history: int) -> np.ndarray:
    """Each tenant's start in the stream for one replay pass."""
    return rng.integers(0, n_snaps - history + 1, size=tenants)


def zipf_counts(tenants: int, rate: float, seconds: float, s: float,
                rng: np.random.Generator) -> np.ndarray:
    """Arrivals per tenant: Zipf(s) shares of ``rate * seconds``, the
    shares dealt to the tenants in a seeded order."""
    w = 1.0 / np.arange(1, tenants + 1) ** s
    counts = np.rint(rate * seconds * w / w.sum()).astype(np.int64)
    return counts[rng.permutation(tenants)]


def open_loop(rng: np.random.Generator, n_snaps: int, tenants: int,
              rate: float, seconds: float, zipf_s: float) -> list:
    """Per tenant ``(offset, due_s)``: where its walk through the stream
    starts, and the sorted due times of its snapshots in ``[0, seconds)``."""
    counts = zipf_counts(tenants, rate, seconds, zipf_s, rng)
    return [(int(rng.integers(0, n_snaps)),
             np.sort(rng.uniform(0.0, seconds, size=int(c))))
            for c in counts]


def walk(n_snaps: int, offset: int, count: int) -> list:
    """Stream indices of a tenant that walks the stream from ``offset``
    and wraps at its end."""
    return [(offset + k) % n_snaps for k in range(count)]


def sojourns_ms(due_s: np.ndarray, commit_ms: list) -> np.ndarray:
    """Each committed snapshot's time from its due time to its commit, in
    ms, in stream order. Both clocks start at the window's origin; a
    snapshot that never committed has no entry (the caller counts it
    failed)."""
    k = min(len(due_s), len(commit_ms))
    return np.asarray(commit_ms[:k], np.float64) - 1e3 * np.asarray(
        due_s[:k], np.float64)
