"""The structure generator keeps to what its sources file states: atom counts,
elements, radius graphs within the cutoff and the neighbour cap, and
no two atoms closer than the jittered lattice allows."""
import numpy as np
import pytest

from perfbench_testkit import tiny_sources

from perfbench import atoms

SPEC = tiny_sources(total=300)
NAMES, SOURCES = atoms.generate(SPEC)


@pytest.mark.parametrize("name", sorted(SPEC["sources"]))
def test_source_geometry(name):
    p, src = SPEC["sources"][name], SOURCES[NAMES.index(name)]
    lo, hi = p["n_atoms"]
    rho_hi = p["density"][1]
    min_gap = (1 - 2 * SPEC["jitter"]) * rho_hi ** (-1 / 3)
    for i in range(src["species"].shape[0]):
        n = int(src["node_mask"][i].sum())
        assert lo <= n <= hi
        assert set(src["species"][i, :n].tolist()) <= set(p["elements"])
        assert not src["species"][i, n:].any()
        pos = src["pos"][i, :n].astype(float)
        d = np.sqrt(((pos[:, None] - pos[None]) ** 2).sum(-1))
        np.fill_diagonal(d, np.inf)
        assert d.min() >= min_gap * 0.999
        em = src["edge_mask"][i]
        s, t = src["edge_src"][i][em], src["edge_dst"][i][em]
        assert np.all(d[s, t] < SPEC["cutoff"])
        assert np.bincount(t, minlength=n).max() <= SPEC["max_neighbours"]
        # a destination keeps its nearest neighbours: every pair within the
        # cutoff is an edge unless the destination is full
        full = np.bincount(t, minlength=n) == SPEC["max_neighbours"]
        adj = np.zeros((n, n), bool)
        adj[s, t] = True
        missing = (d < SPEC["cutoff"]) & ~adj
        assert not missing[:, ~full].any()


def test_graphs_are_dense_as_stated():
    """Molecules of up to 35 atoms at 5 Angstrom are nearly whole graphs;
    across the sources an atom has some ten or more neighbours."""
    per_atom = [src["edge_mask"].sum() / src["node_mask"].sum()
                for src in SOURCES]
    assert min(per_atom) > 8.0, per_atom
