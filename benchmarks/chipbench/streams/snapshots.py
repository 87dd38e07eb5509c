"""The snapshot stream: each step is one graph snapshot, its edges as
global-id COO triples ``(src, dst, edge_feat)``.

``graph_stream`` is a copy of the program's
``repro.graph.synthetic.generate_temporal_graph`` followed by its
one-unit time splitter (every snapshot's edges share one timestamp, so
slicing keeps each snapshot whole and in generation order), kept here so
that no change to the program moves the benchmark's inputs. It is seeded
from the run's ``--seed`` instead of a fixed dataset seed, draws its
nodes from the dataset's own ``global_nodes`` (the copy's source takes six
times the largest snapshot), and a stream whose snapshot would not fit
the plan's padded sizes (``n_pad``, ``e_pad``, ``k_max``) is drawn again
from the next sub-seed.

The reference reads a snapshot prepared from its raw COO edges: active
nodes renumbered in ascending global id, edges made undirected, a
self-loop per node, and the symmetric GCN coefficient ``1 / sqrt(deg(u)
deg(v))`` over in-degrees of that graph. The arrays are padded to fixed
sizes only so that one compiled scan serves every stream: padding edges
carry coefficient 0 into a spare segment, padding nodes read and write a
spare store row, and neither reaches a compared number.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.traffic import rng_for

WORK = ("snapshot_flops", "snapshot_bytes")


@dataclasses.dataclass
class Snapshots:
    snaps: list  # (src, dst, edge_feat) global-id COO triples, time order
    feat: np.ndarray
    n_global: int
    n_rows: int  # the plan's n_pad: rows of a prepared snapshot
    n_edges: int  # the plan's e_pad: edges of a prepared snapshot

    def __len__(self) -> int:
        return len(self.snaps)


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


def max_in_degree(src: np.ndarray, dst: np.ndarray) -> int:
    """Largest in-degree of the undirected graph with self-loops."""
    active = np.unique(np.concatenate([src, dst]))
    s = np.searchsorted(active, src)
    d = np.searchsorted(active, dst)
    return int(np.bincount(np.concatenate([d, s]),
                           minlength=active.size).max()) + 1


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


def padded(src: np.ndarray, dst: np.ndarray, edge_feat: np.ndarray,
           n_rows: int, n_edges: int, sink: int) -> dict:
    """One snapshot's padded reference arrays (numpy), from global-id COO
    edges. ``sink`` is the spare store row that padding nodes use."""
    active = np.unique(np.concatenate([src, dst]))
    n = active.size
    s = np.searchsorted(active, src)
    d = np.searchsorted(active, dst)
    loops = np.arange(n)
    es = np.concatenate([s, d, loops])
    ed = np.concatenate([d, s, loops])
    ef = np.concatenate([edge_feat, edge_feat,
                         np.zeros((n, edge_feat.shape[1]), np.float32)])
    deg = np.bincount(ed, minlength=n).astype(np.float64)
    coef = 1.0 / np.sqrt(deg[es] * deg[ed])
    m = es.size
    if n > n_rows or m > n_edges:
        raise ValueError(f"snapshot of {n} nodes / {m} edges exceeds the "
                         f"reference buffers ({n_rows}, {n_edges})")
    g = {"rows": np.full(n_rows, sink, np.int32),
         "src": np.zeros(n_edges, np.int32),
         "dst": np.full(n_edges, n_rows, np.int32),
         "coef": np.zeros(n_edges, np.float32),
         "ef": np.zeros((n_edges, edge_feat.shape[1]), np.float32),
         "live": np.int32(1)}
    g["rows"][:n] = active
    g["n"] = n
    g["src"][:m], g["dst"][:m] = es, ed
    g["coef"][:m], g["ef"][:m] = coef, ef
    return g


# ------------------------------------------------------------- the kind ----

def build(cfg: dict, fields: dict, seed: int) -> Snapshots:
    m = cfg["model"]
    snaps, feat, n_global = graph_stream(
        cfg["dataset"], seed, m["in_dim"], m["edge_dim"],
        (fields["n_pad"], fields["e_pad"], fields["k_max"]))
    return Snapshots(snaps, feat, n_global, fields["n_pad"], fields["e_pad"])


def item(stream: Snapshots, k: int):
    from repro.graph.coo import COOSnapshot

    s, d, e = stream.snaps[k]
    return COOSnapshot(src=s, dst=d, edge_feat=e, t_index=k)


def rows(stream: Snapshots, k: int) -> np.ndarray:
    s, d, _ = stream.snaps[k]
    return np.unique(np.concatenate([s, d]))


def live_rows(stream: Snapshots, k: int) -> int:
    return rows(stream, k).size


def work(stream: Snapshots, k: int, family_work, m: dict) -> tuple:
    n, e = live_rows(stream, k), stream.snaps[k][0].size
    return (family_work.snapshot_flops(m, n, e),
            family_work.snapshot_bytes(m, n, e))


def prepare(stream: Snapshots, k: int) -> dict:
    g = padded(*stream.snaps[k], stream.n_rows, stream.n_edges,
               stream.n_global)
    g["x"] = stream.feat[np.minimum(g["rows"], stream.n_global - 1)]
    return g
