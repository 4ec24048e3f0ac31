"""The benchmark's vectorised molecule pool (bench/molecules.py) against the
program's own MolHIV-statistics generator (data/pipeline.MoleculeStream)."""
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import molecules  # noqa: E402

POOL = 65_536
STREAM = 3_000


@pytest.fixture(scope="module")
def pool():
    return molecules.make_pool(20_240_611, POOL)


@pytest.fixture(scope="module")
def stream_sizes():
    from repro.data.pipeline import MOLHIV, MoleculeStream

    graphs = MoleculeStream(MOLHIV, seed=3).take(STREAM)
    return (np.array([g[2].shape[0] for g in graphs]),
            np.array([len(g[0]) for g in graphs]))


def _close(a, b, se_a, se_b, k=4.0):
    return abs(a - b) <= k * np.hypot(se_a, se_b)


@pytest.mark.parametrize("which", ["nodes", "edges"])
def test_pool_sizes_follow_the_program_generator(pool, stream_sizes, which):
    mine = pool.nodes if which == "nodes" else pool.edges
    theirs = stream_sizes[0] if which == "nodes" else stream_sizes[1]
    sd_m, sd_t = mine.std(), theirs.std()
    assert _close(mine.mean(), theirs.mean(),
                  sd_m / np.sqrt(len(mine)), sd_t / np.sqrt(len(theirs)))
    assert _close(sd_m, sd_t, sd_m / np.sqrt(2 * len(mine)),
                  sd_t / np.sqrt(2 * len(theirs)))


@pytest.mark.parametrize("cut", [4, 32, 64])
def test_pool_size_shares_follow_the_program_generator(pool, stream_sizes, cut):
    """P(n <= 4) (the clip), P(n > 32) and P(n > 64): the shares of
    molecules that land in each base bucket of the executor."""
    test = (lambda n: n <= cut) if cut == 4 else (lambda n: n > cut)
    p, q = test(pool.nodes).mean(), test(stream_sizes[0]).mean()
    se = lambda x, n: np.sqrt(max(x * (1 - x), 1e-4) / n)  # noqa: E731
    assert _close(p, q, se(p, POOL), se(q, STREAM))


def test_each_molecule_is_a_symmetric_tree_plus_ring_closures(pool):
    for i in range(0, POOL, 4099):
        s, r, nf, ef = pool.graph(i)
        n = nf.shape[0]
        assert n >= 4 and nf.shape[1] == 9 and ef.shape == (len(s), 3)
        assert len(s) == 2 * (n - 1) + 2 * int(n * 0.2 / 2.0 + 1e-9)
        assert 0 <= min(s.min(), r.min()) and max(s.max(), r.max()) < n
        assert sorted(zip(s, r)) == sorted(zip(r, s))  # both directions
        tree = s[: n - 1]
        assert (tree == np.arange(1, n)).all() and (r[: n - 1] < tree).all()


def test_the_pool_holds_no_repeated_molecule(pool):
    digests = set()
    for i in range(POOL):
        h = hashlib.blake2b(digest_size=16)
        for part in pool.graph(i):
            h.update(np.ascontiguousarray(part).tobytes())
        digests.add(h.digest())
    assert len(digests) == POOL


def test_the_same_seed_gives_the_same_pool():
    a, b = molecules.make_pool(7, 64), molecules.make_pool(7, 64)
    c = molecules.make_pool(8, 64)
    for x, y in zip(a.graph(63), b.graph(63)):
        assert np.array_equal(x, y)
    assert not np.array_equal(a.node_feat, c.node_feat[: len(a.node_feat)])
