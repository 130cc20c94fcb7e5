"""The seam between the shared yardstick and an architecture's reference
module: the EGNN's weights as they were pinned, the same parameter layout
as the program's own, and a toy architecture that runs through the shared
files without an edit to them."""
import dataclasses
import glob
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench_testkit import tiny_config, tiny_sources, tiny_traffic
from perfbench import atoms, flops, harness, peaks, program, weights
from perfbench.reference import common

# SHA-256 over every leaf (path, dtype, shape, bytes) of init_params at the
# reference's tiny sizes for seed 7, recorded before the EGNN's init moved
# from weights.py into its reference module
PINNED = {
    "hydragnn-gfm":
        "4c21836bfaf257ea8ef1ffe72209c75abca284f6e50f88ea95639ccdb26dace1",
    "hydragnn-gfm-baseline":
        "ca732f7a4f43bfd34317459725ea94230e67ec31a2490774d5c302c4d42d94cc",
}

TOY = {"name": "toy-invariant",
       "reference": "tests/fixtures/toy_invariant.py", "width": 32,
       "n_species": 6, "n_tasks": 2, "param_dtype": "float32"}

ARCH_WORDS = ("gnn_hidden", "gnn_layers", "phi_e", "phi_h")


def digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        leaf = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {leaf.dtype} {leaf.shape}\n"
                 .encode())
        h.update(leaf.tobytes())
    return h.hexdigest()


def layout(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, jnp.dtype(x.dtype)),
                                  tree)


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_egnn_weights_are_pinned(name):
    assert digest(weights.init_params(tiny_config(name), 7)) == PINNED[name]


def test_init_params_has_the_programs_layout(bench):
    """For every configuration, the weights the yardstick draws have the
    tree, shapes and dtypes of the program's own initial parameters."""
    names, sources = atoms.generate(tiny_sources())
    for c in bench["configs"]:
        cell = next(w for w in bench["workloads"] if w["config"] == c["name"]
                    and harness.load_json(os.path.join(
                        harness.BENCH_DIR, "traffic", w["traffic"] + ".json"))
                    ["runner"] == "train")
        cfg = tiny_config(c["name"])
        sess = program.session(cfg, tiny_traffic(cell["traffic"]), sources,
                               names, seed=0)
        try:
            assert layout(weights.init_params(cfg, 7)) \
                == layout(sess.state.params), c["name"]
        finally:
            sess.close()


def test_arch_takes_every_field_the_file_names(bench):
    from repro import configs
    sizes = {"gnn_hidden", "gnn_layers", "head_hidden", "head_layers",
             "n_tasks", "n_species", "max_atoms", "max_edges"}
    for c in bench["configs"]:
        cfg = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        base = configs.get(cfg["arch"])
        fields = {f.name for f in dataclasses.fields(base)}
        assert set(cfg) & fields - {"name"} \
            == sizes | {"compute_dtype", "param_dtype"}
        got = program.arch(cfg)
        assert got == base.replace(
            **{k: cfg[k] for k in sizes},
            compute_dtype=weights.DTYPES[cfg["compute_dtype"]],
            param_dtype=weights.DTYPES[cfg["param_dtype"]])
        assert got.name == base.name


def test_toy_architecture_weights_and_count():
    ref = harness.reference(TOY)
    cfg = ref.tiny(TOY)
    params = weights.init_params(cfg, 2 ** 33 + 7)
    assert layout(params) == {
        "shared": {"embed": ((6, 4), jnp.dtype("float32"))},
        "heads": {"out": ((2, 4, 4), jnp.dtype("float32"))}}
    assert digest(params) == digest(weights.init_params(cfg, 2 ** 33 + 7))
    assert digest(params) != digest(weights.init_params(cfg, 7))
    # energy: 4 MACs per structure; forces: 12 per atom
    assert flops.train_step_flops(cfg, structures=2, atoms=5, edges=9) \
        == 3 * 2 * (2 * 4 + 5 * 12)
    per_step = [{"structures": 2, "atoms": 5, "edges": 9}] * 3
    record = {"kind": "train", "config": cfg, "per_step": per_step,
              "device_kind": "TPU v5 lite", "window_s": 0.5, "chips": 1}
    assert harness.read_metric("train_mfu", record) == pytest.approx(
        100.0 * 3 * 3 * 2 * (2 * 4 + 5 * 12)
        / (0.5 * peaks.peak("TPU v5 lite", "bf16_flops_per_s")), rel=1e-12)


def toy_batches(cfg, n, *, T=2, B=3, A=5):
    rng = np.random.default_rng(0)
    out = []
    for _ in range(n):
        nm = rng.random((T, B, A)) < 0.7
        nm[..., 0] = True
        out.append({
            "species": jnp.asarray(np.where(nm, rng.integers(
                1, cfg["n_species"], (T, B, A)), 0), jnp.int32),
            "node_mask": jnp.asarray(nm),
            "pos": jnp.asarray(rng.normal(size=(T, B, A, 3)), jnp.float32),
            "energy": jnp.asarray(rng.normal(size=(T, B)), jnp.float32),
            "forces": jnp.asarray(rng.normal(size=(T, B, A, 3)),
                                  jnp.float32)})
    return out


def toy_loss(params, batch, weights_):
    """The loss of the toy, in NumPy float64."""
    emb = np.asarray(params["shared"]["embed"], float)
    out = np.asarray(params["heads"]["out"], float)
    total = 0.0
    for t, w in enumerate(weights_):
        nm = np.asarray(batch["node_mask"][t], float)
        h = emb[np.asarray(batch["species"][t])] * nm[..., None]
        pooled = h.sum(1) / np.maximum(nm.sum(-1, keepdims=True), 1)
        e = pooled @ out[t][:, 0]
        f = (h @ out[t][:, 1:]) * nm[..., None]
        e_err = np.mean((e - np.asarray(batch["energy"][t])) ** 2)
        f_err = np.sum((f - np.asarray(batch["forces"][t])) ** 2
                       * nm[..., None]) / max(nm.sum() * 3.0, 1.0)
        total += w * (e_err + f_err)
    return total


def test_toy_architecture_trains_through_the_shared_readings():
    ref = harness.reference(TOY)
    cfg = ref.tiny(TOY)
    params = weights.init_params(cfg, 11)
    batches = toy_batches(cfg, 3)
    w = np.asarray([0.25, 0.75])
    hp = {"lr": 1e-2, "warmup": 1, "schedule_steps": 10,
          "weight_decay": 0.01}
    got = common.train_readings(ref.trunk, ref.branch, params, batches, w,
                                hp, cfg)
    assert len(got["loss"]) == 3
    assert got["loss"][0] == pytest.approx(toy_loss(params, batches[0], w),
                                           rel=1e-5)
    assert len(got["grad_norms"]) == len(got["change_norms"]) == 2
    assert min(got["grad_norms"]) > 0 and min(got["change_norms"]) > 0
    # the serving readings pick each structure's own head
    rows = {k: v[0] for k, v in batches[0].items()}
    e0, e1, mixed = (common.serve_readings(ref.trunk, ref.branch, params,
                                           rows, heads, cfg)[0]
                     for heads in ([0, 0, 0], [1, 1, 1], [0, 1, 0]))
    np.testing.assert_array_equal(mixed, [e0[0], e1[1], e0[2]])


def test_shared_files_name_no_architecture():
    """Everything of the yardstick outside ``reference/`` and ``tests/`` is
    architecture-neutral: a configuration of another architecture needs
    new files only."""
    shared = [p for p in glob.glob(os.path.join(harness.BENCH_DIR, "**",
                                                "*.py"), recursive=True)
              if os.path.relpath(p, harness.BENCH_DIR).split(os.sep)[0]
              not in ("reference", "tests")]
    for name in ("weights.py", "flops.py", "program.py",
                 os.path.join("runners", "train.py"),
                 os.path.join("runners", "serve.py")):
        assert os.path.join(harness.BENCH_DIR, name) in shared
    for path in shared:
        with open(path) as f:
            text = f.read()
        assert not [w for w in ARCH_WORDS if w in text], path
