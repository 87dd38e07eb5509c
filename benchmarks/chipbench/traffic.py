"""Seeded traffic: the graph stream and the schedules served from it.

``graph_stream`` is a copy of the program's
``repro.graph.synthetic.generate_temporal_graph`` followed by its
one-unit time splitter (every snapshot's edges share one timestamp, so
slicing keeps each snapshot whole and in generation order), kept here so
that no change to the program moves the benchmark's inputs. It is seeded
from the run's ``--seed`` instead of a fixed dataset seed, draws its
nodes from the dataset's own ``global_nodes`` (the copy's source takes six
times the largest snapshot), and a stream whose snapshot would not fit
the configuration's padded buckets is drawn again from the next
sub-seed.

The schedules give every seed the same amount of work in another order:
a replay pass is a fixed number of tenants each replaying a fixed-length
run of the stream, and an open-loop window fixes each tenant's number of
arrivals (its Zipf share of the aggregate rate times the window) and
draws their times uniformly over the window, which is a Poisson process
conditioned on its count.
"""
from __future__ import annotations

import numpy as np

from chipbench.refcore import max_in_degree


def rng_for(seed: int, *purpose: int) -> np.random.Generator:
    """An independent generator for one use of the run's seed."""
    return np.random.default_rng(np.random.SeedSequence([seed, *purpose]))


def _generate(ds: dict, rng: np.random.Generator, feat_dim: int,
              edge_dim: int) -> tuple:
    n_global = ds["global_nodes"]
    src_all, dst_all = [], []
    pop = np.ones(n_global, np.float64)
    for _ in range(ds["snapshots"]):
        e = int(np.clip(rng.lognormal(np.log(ds["avg_edges"]), 0.45), 8,
                        ds["max_edges"]))
        ws = int(np.clip(rng.lognormal(np.log(ds["avg_nodes"]), 0.35), 8,
                         ds["max_nodes"]))
        p = pop / pop.sum()
        cand = rng.choice(n_global, size=ws, replace=False, p=p)
        s = rng.choice(cand, size=e)
        d = rng.choice(cand, size=e)
        keep = s != d
        s, d = s[keep], d[keep]
        src_all.append(s)
        dst_all.append(d)
        np.add.at(pop, s, 1.0)
        np.add.at(pop, d, 1.0)
    total = sum(s.size for s in src_all)
    ef = rng.normal(0, 1, (total, edge_dim)).astype(np.float32)
    feat = rng.normal(0, 1, (n_global, feat_dim)).astype(np.float32)
    snaps, at = [], 0
    for s, d in zip(src_all, dst_all):
        if s.size:  # an empty time window is dropped, as the splitter does
            snaps.append((s, d, ef[at:at + s.size]))
        at += s.size
    return snaps, feat, n_global


def fits(snap: tuple, n_pad: int, e_pad: int, k_max: int) -> bool:
    """Whether a snapshot fits the padded bucket (nodes, undirected edges
    with self-loops, in-degree)."""
    s, d, _ = snap
    n = np.unique(np.concatenate([s, d])).size
    return (n <= n_pad and 2 * s.size + n <= e_pad
            and max_in_degree(s, d) <= k_max)


def graph_stream(ds: dict, seed: int, feat_dim: int, edge_dim: int,
                 bucket: tuple) -> tuple:
    """``(snapshots, feat_table, n_global)`` for this seed: snapshots as
    ``(src, dst, edge_feat)`` global-id COO triples in time order."""
    for sub in range(100):
        snaps, feat, n_global = _generate(ds, rng_for(seed, 0, sub),
                                          feat_dim, edge_dim)
        if all(fits(s, *bucket) for s in snaps):
            return snaps, feat, n_global
    raise ValueError(f"no stream of {ds} fits bucket {bucket} in 100 "
                     "sub-seeds")


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
