"""Seeded-sweep property tests for the data pipeline.

These sweeps draw their own randomized configurations from seeded NumPy
generators rather than ``hypothesis`` — deterministic, ≥ 50 drawn
configurations per property, the same every run — and assert the
subsystem invariants the docs promise:

  * ``BucketingBatcher`` never drops content: trimming only removes
    trailing pad, every real atom/edge value survives bit-identical, and
    the trimmed batch keeps the ``>= A_pad`` edge-sentinel contract the
    kernels rely on (``docs/kernels.md``).
  * ``MixingBatcher``'s deterministic schedule tracks the target weights
    within the documented bound: after k batches every source's cumulative
    count is within ``len(sources)`` of ``k·B·w_s`` — not just in
    expectation.
"""
import numpy as np

from repro.data.bucketing import (ATOM_KEYS, EDGE_KEYS, BucketingBatcher,
                                  BucketSpec)
from repro.data.mixing import MixingBatcher, MixingConfig

N_CONFIGS = 60      # ≥ 50 drawn configurations per property


# ---------------------------------------------------------------------------
# BucketingBatcher: trimming is content-exact and sentinel-valid
# ---------------------------------------------------------------------------

class _RandomFrontPackedBatcher:
    """Emits flat (B, A, ...) batches with front-packed masks and random
    per-sample content sizes — the contract every store in this repo
    satisfies, with full control over the drawn shapes."""

    def __init__(self, rng, B, A, E):
        self.rng, self.B, self.A, self.E = rng, B, A, E

    def next_batch(self):
        rng, B, A, E = self.rng, self.B, self.A, self.E
        na = rng.integers(1, A + 1, size=B)            # content atoms
        ne = rng.integers(0, E + 1, size=B)            # content edges
        nm = np.arange(A)[None, :] < na[:, None]
        em = np.arange(E)[None, :] < ne[:, None]
        src = rng.integers(0, np.maximum(na, 1)[:, None], (B, E))
        dst = rng.integers(0, np.maximum(na, 1)[:, None], (B, E))
        batch = {
            "species": rng.integers(1, 9, (B, A)) * nm,
            "pos": rng.normal(size=(B, A, 3)).astype(np.float32) * nm[..., None],
            "forces": rng.normal(size=(B, A, 3)).astype(np.float32) * nm[..., None],
            "node_mask": nm,
            "edge_src": np.where(em, src, A).astype(np.int32),
            "edge_dst": np.where(em, dst, A).astype(np.int32),
            "edge_mask": em,
            "energy": rng.normal(size=(B,)).astype(np.float32),
        }
        return batch


def _draw_spec(rng, A, E):
    a_cuts = np.unique(rng.integers(1, A, size=rng.integers(1, 4)))
    e_cuts = np.unique(rng.integers(1, E, size=rng.integers(1, 4)))
    return BucketSpec(tuple(int(c) for c in a_cuts) + (A,),
                      tuple(int(c) for c in e_cuts) + (E,))


def test_bucketing_never_drops_content_sweep():
    """≥ 50 random (B, A, E, bucket-grid) configurations: every batch the
    trimmer emits is the wrapped batch minus trailing pad, nothing else."""
    for seed in range(N_CONFIGS):
        rng = np.random.default_rng(1000 + seed)       # config draws only
        B = int(rng.integers(1, 7))
        A = int(rng.integers(4, 40))
        E = int(rng.integers(4, 90))
        spec = _draw_spec(rng, A, E)
        # two identical content streams: one trimmed, one raw mirror
        inner = _RandomFrontPackedBatcher(
            np.random.default_rng((1000 + seed, 1)), B, A, E)
        mirror = _RandomFrontPackedBatcher(
            np.random.default_rng((1000 + seed, 1)), B, A, E)
        bb = BucketingBatcher(inner, spec)
        for _ in range(3):
            raw = mirror.next_batch()
            out = bb.next_batch()
            A_t = out["node_mask"].shape[-1]
            E_t = out["edge_mask"].shape[-1]
            # the emitted shape is a grid shape, the SMALLEST one that holds
            # the content
            assert (A_t, E_t) == spec.ceil(int(raw["node_mask"].sum(-1).max()),
                                           int(raw["edge_mask"].sum(-1).max()))
            # no content dropped: mask mass conserved ...
            assert out["node_mask"].sum() == raw["node_mask"].sum()
            assert out["edge_mask"].sum() == raw["edge_mask"].sum()
            # ... and every surviving value is bit-identical to the source
            for k in ATOM_KEYS:
                if k in raw:
                    np.testing.assert_array_equal(out[k], raw[k][:, :A_t],
                                                  err_msg=k)
            for k in ("edge_mask",):
                np.testing.assert_array_equal(out[k], raw[k][:, :E_t])
            # untouched passthrough keys
            np.testing.assert_array_equal(out["energy"], raw["energy"])


def test_bucketing_trimmed_edges_stay_sentinel_valid_sweep():
    """≥ 50 random configurations: in every trimmed batch, masked edges
    point at the TRIMMED pad sentinel (>= A_t) and real edges keep their
    original in-range endpoints — the kernels' ``>= n_nodes`` contract."""
    for seed in range(N_CONFIGS):
        rng = np.random.default_rng(7000 + seed)       # config draws only
        B = int(rng.integers(1, 6))
        A = int(rng.integers(4, 32))
        E = int(rng.integers(4, 70))
        spec = _draw_spec(rng, A, E)
        inner = _RandomFrontPackedBatcher(
            np.random.default_rng((7000 + seed, 1)), B, A, E)
        mirror = _RandomFrontPackedBatcher(
            np.random.default_rng((7000 + seed, 1)), B, A, E)
        bb = BucketingBatcher(inner, spec)
        for _ in range(3):
            raw = mirror.next_batch()
            out = bb.next_batch()
            A_t = out["node_mask"].shape[-1]
            E_t = out["edge_mask"].shape[-1]
            em = out["edge_mask"]
            for k in ("edge_src", "edge_dst"):
                assert (out[k][~em] >= A_t).all(), \
                    f"masked {k} below the trimmed sentinel"
                assert (out[k][em] < A_t).all(), f"real {k} out of range"
                np.testing.assert_array_equal(out[k][em], raw[k][:, :E_t][em],
                                              err_msg=k)
            # real edges only reference real (unmasked) nodes
            per_row_atoms = out["node_mask"].sum(-1)
            assert (out["edge_src"][em]
                    < np.broadcast_to(per_row_atoms[:, None], em.shape)[em]).all()


# ---------------------------------------------------------------------------
# MixingBatcher: realized counts track the target weights
# ---------------------------------------------------------------------------

def _mix_sources(rng, n_sources):
    sizes = rng.integers(3, 60, size=n_sources)
    return [{"x": (1000 * s + np.arange(n)).astype(np.int64)}
            for s, n in enumerate(sizes)], sizes


def test_mixing_counts_track_weights_sweep():
    """≥ 50 random (sources, B, temperature/explicit-weights, seed)
    configurations: cumulative per-source counts stay within the documented
    bound (len(sources)) of k·B·w_s at EVERY k."""
    for seed in range(N_CONFIGS):
        rng = np.random.default_rng(3000 + seed)
        n_sources = int(rng.integers(1, 6))
        sources, sizes = _mix_sources(rng, n_sources)
        if rng.random() < 0.5:
            mix = MixingConfig(temperature=float(rng.uniform(0.5, 4.0)),
                               emit_source=True)
        else:
            mix = MixingConfig(weights=tuple(rng.uniform(0.2, 3.0,
                                                         n_sources)),
                               emit_source=True)
        B = int(rng.integers(1, 18))
        mb = MixingBatcher(sources, B, mixing=mix, seed=seed)
        counts = np.zeros(n_sources)
        for k in range(1, 13):
            batch = mb.next_batch()
            assert batch["x"].shape[0] == B          # exact batch size
            counts += np.bincount(batch["source_id"], minlength=n_sources)
            dev = np.abs(counts - k * B * mb.weights).max()
            assert dev <= n_sources, \
                f"seed={seed}: drift {dev:.2f} > {n_sources} at batch {k}"


def test_mixing_stream_is_lossless_per_source_sweep():
    """≥ 50 configurations: within any window, the samples drawn from a
    source are distinct until its local epoch wraps (shuffled-cyclic — the
    mixture never repeats a sample before exhausting its source)."""
    for seed in range(N_CONFIGS):
        rng = np.random.default_rng(5000 + seed)
        n_sources = int(rng.integers(1, 5))
        sources, sizes = _mix_sources(rng, n_sources)
        B = int(rng.integers(2, 12))
        mb = MixingBatcher(sources, B,
                           mixing=MixingConfig(emit_source=True), seed=seed)
        drawn = [[] for _ in range(n_sources)]
        for _ in range(6):
            b = mb.next_batch()
            for s in range(n_sources):
                drawn[s].extend(b["x"][b["source_id"] == s].tolist())
        for s, n in enumerate(sizes):
            vals = np.asarray(drawn[s], np.int64)
            assert ((vals >= 1000 * s) & (vals < 1000 * s + n)).all()
            # shuffled-cyclic: over f = len//n full epochs every sample is
            # drawn f or f+1 times, and exactly len%n samples got the extra
            # draw (order-independent — batch composition shuffles draws)
            full, rest = divmod(len(vals), n)
            hist = np.bincount(vals - 1000 * s, minlength=n)
            assert hist.min() >= full and hist.max() <= full + 1, \
                f"seed={seed}, source {s}: non-cyclic draw"
            assert int((hist == full + 1).sum()) == rest
