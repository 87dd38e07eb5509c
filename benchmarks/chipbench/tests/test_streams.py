"""CPU tests of the stream kinds (``chipbench/streams/``): the snapshot
kind's generator, and a golden record of what the three cells read.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chipbench/tests

The golden values were recorded with the harness as it stood before the
snapshot stream moved into ``streams/snapshots.py`` (PR 14's tree), at the
configurations' full sizes. The moved code has to reproduce them: the
stream's bytes, the feature table the server is given, the replay offsets
and the open-loop schedule, the prepared reference arrays and work counts
of a 64-step stream, and the reference's outputs and final state over it.
"""
from __future__ import annotations

import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "benchmarks")]

from chipbench import catalog, driver, refcore, traffic  # noqa: E402
from chipbench.streams import snapshots  # noqa: E402

DS = {"name": "tiny", "avg_nodes": 12, "avg_edges": 20, "max_nodes": 28,
      "max_edges": 48, "snapshots": 24, "global_nodes": 90}


def test_graph_stream_is_deterministic_per_seed_and_seeds_differ():
    a = snapshots.graph_stream(DS, 2**31 + 5, 8, 4, (32, 128, 24))
    b = snapshots.graph_stream(DS, 2**31 + 5, 8, 4, (32, 128, 24))
    c = snapshots.graph_stream(DS, 2**31 + 6, 8, 4, (32, 128, 24))
    for (s1, d1, e1), (s2, d2, e2) in zip(a[0], b[0]):
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(e1, e2)
    np.testing.assert_array_equal(a[1], b[1])
    assert not all(s1.shape == s2.shape and (s1 == s2).all()
                   for (s1, _, _), (s2, _, _) in zip(a[0], c[0]))
    assert all(snapshots.fits(s, 32, 128, 24) for s in a[0])


# --------------------------------------------------------------- golden ----

STEPS = 64  # the reference's stream: the first replay history
ONLINE_SECONDS = 45.0  # the benchmark's run_seconds

STREAM = {  # per seed: the same for both configurations
    2147483749: {
        "steps": 137, "n_global": 3783,
        "items": "50d3b9d98d9db50d534e033b98543af3"
            "cf3feb5afa45dd593ebd1a5b2020f636",
        "feat": "33e14c28b2dbd4470413e83e5c47ee75"
            "0fe80631d9c260c098e615394a8c3126",
        "rows": "f98509f2e049cc10d4fae06032b7a8b2"
            "5a3ed810ca23949ebef4bf219d9ba5d0",
        "live_rows": "920e5a2094049df18a29769c5fdba68b"
            "bbe055d6bfeeaaf7249b0b3d60eb7549",
        "prepared": "af44d2a34b5fe4df29ef79de6457e4bd"
            "ff7b84c85f6861de6319603e08689139",
        "bytes": "5718017004970f753397e6dbe651e254"
            "a71680c136c9280831a227a305ae67f6",
        "replay_offsets": "f9c538a9e70a3daad992bc28d3c43d22"
            "aa5daace540b542c31ab20a2ed2e63ac",
        "schedule": "2bdb9d2a09417e051a5b5577fbada280"
            "c3bc708fcc7febfedd451fb74c5e2b5a",
        "bytes_sum": 4348392,
    },
    3000000007: {
        "steps": 137, "n_global": 3783,
        "items": "5f6aed50789b1fa55e5dfb9d7bd9f67e"
            "e4f9418bb2a9f3f31d8ebbef232942be",
        "feat": "2d9a7efb33b60de7cb19511d61555ebc"
            "eafbe3deba2c400532bd92dbabdd222d",
        "rows": "f6be95ca282b9ac736d322dd66cd50b5"
            "d451a15a5bc03e832b836810afdedf55",
        "live_rows": "adee0b59f0839278f8db881a3c5ce439"
            "ebf00f50140559369d8732e2eb625992",
        "prepared": "859e1054789b0090d894c11e73364a0c"
            "97d21f59b3fdc520becf988cbbf251c2",
        "bytes": "e49518dd217d7288a5b07de924b91785"
            "7cb66454fa22bfbacca55ee8f4abd09b",
        "replay_offsets": "d6198571502b34420f09c3d74f7af636"
            "51c74a6830d652ba1ff2bdd39da3d881",
        "schedule": "6f3b3de4a0f87deb2bf1eddd6fbf5234"
            "00828caa6b3341bc66d314079cb0b53d",
        "bytes_sum": 4295020,
    },
}
MODEL = {  # per configuration and seed
    ("gcrn-m2-bcalpha", 2147483749): {
        "flops": "3992a4eb68409283c7c3951506a54348"
            "2762013bbaf1800f9b5de724793ed4be",
        "flops_sum": 1548703392,
        "outputs": (101.55309345702388,
                    1832.454620586941),
        "state": (108.24526104777823, 4492.835172181717),
    },
    ("gcrn-m2-bcalpha", 3000000007): {
        "flops": "14a8e0d0a994de9baad66d7c8ad74791"
            "353ba81bed0480f63b1cf9a4b5cb176f",
        "flops_sum": 1495183280,
        "outputs": (100.97337863689178,
                    1506.1813980241495),
        "state": (98.60325661325713, -5244.700872046155),
    },
    ("evolvegcn-o-bcalpha", 2147483749): {
        "flops": "2a840c366af4b46937f510ab1c07c831"
            "1655657993902b982b5d07d21c1d2efd",
        "flops_sum": 1481203264,
        "outputs": (465.8010996985121,
                    -188912.65414880274),
        "state": (18.242871789496974, -42.324639819562435),
    },
    ("evolvegcn-o-bcalpha", 3000000007): {
        "flops": "b45a4110b52b25cbd887124d0786cf85"
            "57a2ae8a18479b7fc1c7c253640277f3",
        "flops_sum": 1473533664,
        "outputs": (301.3121896968423,
                    -86879.28104905144),
        "state": (22.415665918290248, 51.781478161981795),
    },
}


def digest(arrays) -> str:
    """SHA-256 over each array's dtype, shape and bytes, in order."""
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def norm_and_sum(arrays) -> tuple:
    flat = np.concatenate([np.asarray(a, np.float64).ravel()
                           for a in arrays])
    return float(np.linalg.norm(flat)), float(flat.sum())


def close(got: tuple, want: tuple) -> bool:
    """The 2-norm to 1e-6 relative, the sum to 1e-6 of the norm (a sum
    may cancel to near zero): XLA CPU builds may round differently."""
    (n, s), (wn, ws) = got, want
    return abs(n - wn) <= 1e-6 * wn and abs(s - ws) <= 1e-6 * wn


@pytest.mark.parametrize("name,seed", sorted(MODEL))
def test_what_the_cells_read_is_unchanged(name, seed):
    import jax

    want = {**STREAM[seed], **MODEL[name, seed]}
    cfg = catalog.config(name)
    replay = catalog.traffic("bcalpha-replay16")
    online = catalog.traffic("bcalpha-online32-gcrn")
    cell = driver.Cell(cfg, replay, seed)
    stream, runner = cell.stream, cell.runner
    n = len(stream.items)
    assert (n, stream.n_global) == (want["steps"], want["n_global"])
    items = [a for c in stream.items
             for a in (c.src, c.dst, c.edge_feat, np.int64(c.t_index))]
    assert digest(items) == want["items"]
    assert digest([runner.server.feat_table]) == want["feat"]
    assert digest(stream.active) == want["rows"]
    assert digest([np.asarray(stream.n)]) == want["live_rows"]
    rng = traffic.rng_for(seed, 3)
    assert digest([np.asarray(runner._offsets(rng), np.int64)
                   for _ in range(3)]) == want["replay_offsets"]
    sched = traffic.open_loop(traffic.rng_for(seed, 3), n, online["tenants"],
                              online["rate_per_s"], ONLINE_SECONDS,
                              online["zipf_s"])
    assert digest([a for off, due in sched
                   for a in (np.int64(off), due)]) == want["schedule"]
    steps = [stream.prepare(k) for k in range(STEPS)]
    assert digest([np.asarray(g[k]) for g in steps
                   for k in sorted(g)]) == want["prepared"]
    flops = np.asarray(stream.flops[:STEPS], np.int64)
    nbytes = np.asarray(stream.bytes[:STEPS], np.int64)
    assert digest([flops]) == want["flops"]
    assert digest([nbytes]) == want["bytes"]
    assert (flops.sum(), nbytes.sum()) == (want["flops_sum"],
                                           want["bytes_sum"])
    with cell.precision():
        outs, state = refcore.run_stream(cell.ref, cell.params, cell.m,
                                         stream.n_global, steps, "highest")
    got = (norm_and_sum(outs), norm_and_sum(jax.tree.leaves(state)))
    assert close(got[0], want["outputs"]), got
    assert close(got[1], want["state"]), got
