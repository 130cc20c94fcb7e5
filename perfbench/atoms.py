"""Seeded synthetic atomistic structures: the benchmark's own data generator.

Five sources after the paper's datasets (arXiv 2506.21788 §4.1), each with
its element palette, size range, number density and label fidelity (a
per-element energy shift, a global scale and observation noise over one
shared potential). Positions are in Angstrom and graphs are radius graphs
with a cap on neighbours per atom, as the data file states. The parameters
live in a data file (``traffic/sources/<name>.json``); this module only
reads them. NumPy only: the data is made on the host in bulk, nothing
here touches JAX or the program under test.

The structure set is fixed by the file's ``data_seed``: every run of a cell
sees the same structures, sizes and edge counts, and the run's ``--seed``
only reorders them (batch order, request order). So a run's work does not
depend on its seed, only the order does.
"""
from __future__ import annotations

import numpy as np

SAMPLE_KEYS = ("species", "pos", "edge_src", "edge_dst", "node_mask",
               "edge_mask")
LABEL_KEYS = ("energy", "forces")


def _element_table(n_species: int, seed: int):
    rng = np.random.default_rng(seed)
    site = rng.normal(0.0, 1.0, n_species)
    depth = 0.2 + 0.8 * rng.random(n_species)
    radius = 0.9 + 0.6 * rng.random(n_species)
    return site, depth, radius


def energy_forces(species, pos, table, *, alpha=1.5, width2=16.0):
    """Total energy (N,) and forces (N, A, 3) of a Morse pair potential with
    a Gaussian cut-off plus per-element site energies, in float64."""
    site, depth, radius = table
    mask = species > 0
    d = pos[:, :, None, :] - pos[:, None, :, :]            # (N, A, A, 3)
    r2 = np.sum(d * d, -1) + 1e-6
    r = np.sqrt(r2)
    dep = np.sqrt(depth[species][:, :, None] * depth[species][:, None, :])
    r0 = 0.5 * (radius[species][:, :, None] + radius[species][:, None, :])
    pair = mask[:, :, None] & mask[:, None, :]
    pair &= ~np.eye(species.shape[1], dtype=bool)[None]
    e1 = np.exp(-alpha * (r - r0))
    morse = dep * (e1 * e1 - 2.0 * e1)
    cut = np.exp(-r2 / width2)
    e_pair = np.where(pair, morse * cut, 0.0)
    energy = np.sum(np.where(mask, site[species], 0.0), -1) \
        + 0.5 * e_pair.sum((-1, -2))
    # dE/dr for each ordered pair; each unordered pair appears twice with
    # the 1/2 above, so the force on i sums over j once
    dmorse = dep * (-2.0 * alpha * e1 * e1 + 2.0 * alpha * e1)
    dedr = np.where(pair, dmorse * cut + morse * cut * (-2.0 * r / width2),
                    0.0)
    forces = -np.sum((dedr / r)[..., None] * d, axis=2)
    return energy, forces


def cluster_positions(rng, n, density, jitter):
    """``n`` positions of a compact cluster at ``density`` atoms per cubic
    Angstrom: the ``n`` sites of a simple cubic lattice nearest a random
    centre, each moved by up to ``jitter`` of the spacing along each axis,
    the whole turned by a random rotation."""
    a = density ** (-1.0 / 3.0)
    k = int(np.ceil(n ** (1.0 / 3.0))) + 2
    g = np.arange(-k, k + 1, dtype=float)
    sites = np.stack(np.meshgrid(g, g, g, indexing="ij"), -1).reshape(-1, 3)
    centre = rng.random(3) - 0.5
    near = np.argsort(((sites - centre) ** 2).sum(-1), kind="stable")[:n]
    x = sites[near] + rng.uniform(-jitter, jitter, (n, 3))
    q, r = np.linalg.qr(rng.normal(size=(3, 3)))
    return (x - x.mean(0)) * a @ (q * np.sign(np.diag(r)))


def _radius_edges(pos, n, cutoff, max_neighbours, max_edges, a_pad):
    """Directed edges src -> dst within ``cutoff``, at most
    ``max_neighbours`` per destination atom (its nearest), dst-major."""
    d2 = ((pos[:n, None] - pos[None, :n]) ** 2).sum(-1)
    np.fill_diagonal(d2, np.inf)
    rank = np.argsort(np.argsort(d2, axis=0, kind="stable"), axis=0,
                      kind="stable")
    adj = (d2 < cutoff ** 2) & (rank < max_neighbours)
    dst, src = np.nonzero(adj.T)
    if len(src) > max_edges:
        raise ValueError(f"{len(src)} edges exceed max_edges={max_edges}")
    s = np.full(max_edges, a_pad, np.int32)
    t = np.full(max_edges, a_pad, np.int32)
    em = np.zeros(max_edges, bool)
    s[:len(src)], t[:len(src)], em[:len(src)] = src, dst, True
    return s, t, em


def source_counts(spec: dict) -> dict:
    """Structures per source: ``total`` apportioned by ``rel_size`` (largest
    remainder, at least one each), in the file's source order."""
    names = list(spec["sources"])
    w = np.asarray([spec["sources"][n]["rel_size"] for n in names], float)
    w = w / w.sum()
    total = int(spec["total"])
    counts = np.maximum(np.floor(total * w).astype(int), 1)
    for i in np.argsort(-(total * w - counts), kind="stable"):
        if counts.sum() >= total:
            break
        counts[i] += 1
    return dict(zip(names, (int(c) for c in counts)))


def generate(spec: dict) -> tuple[list, list]:
    """-> (names, sources): one dict of NumPy arrays per source, padded to
    (``max_atoms``, ``max_edges``), with masks front-packed and pad edges
    pointing at the pad sentinel ``max_atoms``."""
    A, E = int(spec["max_atoms"]), int(spec["max_edges"])
    cutoff, cap = float(spec["cutoff"]), int(spec["max_neighbours"])
    n_species = int(spec["n_species"])
    table = _element_table(n_species, int(spec["element_seed"]))
    rng = np.random.default_rng(int(spec["data_seed"]))
    names, sources = [], []
    for name, n_struct in source_counts(spec).items():
        p = spec["sources"][name]
        lo, hi = p["n_atoms"]
        species = np.zeros((n_struct, A), np.int32)
        pos = np.zeros((n_struct, A, 3), np.float32)
        nmask = np.zeros((n_struct, A), bool)
        n_atoms = rng.integers(lo, hi + 1, n_struct)
        rho_lo, rho_hi = p["density"]
        for i, n in enumerate(n_atoms):
            species[i, :n] = rng.choice(p["elements"], n)
            pos[i, :n] = cluster_positions(rng, n, rng.uniform(rho_lo, rho_hi),
                                           float(spec["jitter"]))
            nmask[i, :n] = True
        e_tot = np.zeros(n_struct)
        f = np.zeros((n_struct, A, 3))
        for c in range(0, n_struct, 128):
            sl = slice(c, c + 128)
            e_tot[sl], f[sl] = energy_forces(species[sl],
                                             pos[sl].astype(np.float64),
                                             table)
        shift = rng.normal(0.0, p["shift_mag"], n_species)
        comp = np.stack([(species == z).sum(1) for z in range(n_species)], 1)
        comp[:, 0] = 0
        e_obs = (p["scale"] * e_tot + comp @ shift
                 + rng.normal(0.0, p["noise"], n_struct) * n_atoms)
        f_obs = (p["scale"] * f + rng.normal(0.0, p["noise"], f.shape)) \
            * nmask[..., None]
        es = np.zeros((n_struct, E), np.int32)
        ed = np.zeros((n_struct, E), np.int32)
        em = np.zeros((n_struct, E), bool)
        for i, n in enumerate(n_atoms):
            es[i], ed[i], em[i] = _radius_edges(pos[i], n, cutoff, cap, E, A)
        names.append(name)
        sources.append({
            "species": species, "pos": pos, "edge_src": es, "edge_dst": ed,
            "node_mask": nmask, "edge_mask": em,
            "energy": (e_obs / n_atoms).astype(np.float32),
            "forces": f_obs.astype(np.float32)})
    return names, sources


def row_keys(sources) -> dict:
    """Content fingerprint of every stored structure's real part ->
    (source, row). Lets a check confirm that a batch the program assembled
    holds stored structures, whole, and tell them apart."""
    out = {}
    for s, src in enumerate(sources):
        for i in range(src["species"].shape[0]):
            out[fingerprint(src, i)] = (s, i)
    return out


def fingerprint(arrays: dict, i, lead=()) -> bytes:
    """Bytes of one structure's real atoms and edges (pad stripped)."""
    ix = tuple(lead) + (i,)
    nm = np.asarray(arrays["node_mask"][ix], bool)
    em = np.asarray(arrays["edge_mask"][ix], bool)
    na, ne = int(nm.sum()), int(em.sum())
    parts = [np.asarray(arrays["species"][ix])[:na],
             np.asarray(arrays["pos"][ix])[:na],
             np.asarray(arrays["edge_src"][ix])[:ne],
             np.asarray(arrays["edge_dst"][ix])[:ne],
             np.asarray(arrays["energy"][ix]),
             np.asarray(arrays["forces"][ix])[:na]]
    return b"|".join(np.ascontiguousarray(p).tobytes() for p in parts)
