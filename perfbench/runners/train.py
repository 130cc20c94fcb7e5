"""Training runner: closed-loop steps of the program's own compiled step,
fed by the session's own prefetched batches.

Set-up builds one ``Session`` over the traffic's sources, starts its state
from weights drawn from ``--seed`` and takes the first ``check_steps`` steps
through the same feed and the same compiled step the window uses, keeping
what the check needs: each step's loss, the first gradient as the optimizer
got it (its first moment over 1 - beta1) and the parameters' change over
those steps. It then compiles, on a copy of the state, every batch shape
the window will feed (known from a copy of the session's batcher, drawn
ahead), and measures: steps dispatched back to back, the window ending on
the last state. The host waits for a step only once ``IN_FLIGHT`` later
ones are queued behind it, never for the step it just dispatched.
Afterwards the program is freed and the plain reference follows the same
first steps from the same weights on the same batches.
"""
from __future__ import annotations

import collections
import copy
import gc
import os
import shutil
import threading
import time

import numpy as np

from perfbench import atoms, devtrace, harness, program, weights
from perfbench.reference import common

B1 = 0.9   # AdamW's first-moment decay, the program's and the reference's
# steps dispatched ahead of the oldest unfinished one: enough to keep the
# device fed, few enough that the window ends within a few steps of its
# length (unbounded, JAX queues some thirty steps and the window overruns)
IN_FLIGHT = 2


class CountingFeed:
    """Wraps the session's batcher: records, on the host and as each batch
    is drawn, its bucket shape and its real structures, atoms and edges."""

    def __init__(self, batcher):
        self.batcher = batcher
        self.rows = []

    def next_batch(self):
        b = self.batcher.next_batch()
        self.rows.append(batch_counts(b))
        return b

    def __getattr__(self, name):
        return getattr(self.batcher, name)


def batch_counts(b) -> dict:
    nm, em = np.asarray(b["node_mask"]), np.asarray(b["edge_mask"])
    return {"shape": (nm.shape[-1], em.shape[-1]),
            "structures": int(nm.any(-1).sum()), "atoms": int(nm.sum()),
            "edges": int(em.sum()), "edge_slots": int(em.size)}


def frozen_copy(batcher, sources):
    """A copy of ``batcher`` that draws the same stream; the source arrays,
    which batchers only read, are shared rather than copied."""
    memo = {id(v): v for s in sources for v in s.values()}
    return copy.deepcopy(batcher, memo)


def lookahead(la, n: int) -> dict:
    """One whole batch of each distinct shape among the first n batches
    the batcher copy ``la`` draws."""
    examples = {}
    for _ in range(n):
        b = la.next_batch()
        examples.setdefault(batch_counts(b)["shape"], b)
    return examples


def source_weights(sources, temperature: float, n_tasks: int) -> np.ndarray:
    """Loss weight of each source: size ** (1 / temperature), normalised;
    one branch over the mixture has the single weight 1."""
    if n_tasks == 1:
        return np.ones(1)
    n = np.asarray([s["species"].shape[0] for s in sources], float)
    w = n ** (1.0 / temperature)
    return w / w.sum()


def rows_bad(host_batches, keys, multitask: bool) -> int:
    """Structures in the checked batches that are not a stored structure,
    whole, from the right source, or that repeat."""
    bad, seen = 0, set()
    for b in host_batches:
        T, B = b["node_mask"].shape[:2]
        for t in range(T):
            for i in range(B):
                hit = keys.get(atoms.fingerprint(b, i, lead=(t,)))
                if hit is None or hit in seen or (multitask and hit[0] != t):
                    bad += 1
                seen.add(hit)
    return bad


def leaf_gap(prog, ref, *, skip=None) -> float:
    """Worst leaf's |prog - ref| over max(ref of that leaf, median ref)."""
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    keep = np.ones(ref.shape, bool) if skip is None else ~skip
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref)[keep] / scale[keep]))


def run(*, config, traffic, limits, seed, seconds, trace, devices, t_start,
        log=harness.log, fault=None):
    import jax
    import jax.numpy as jnp

    names, sources = atoms.generate(harness.sources_spec(traffic))
    marks = {"imports_and_data": time.perf_counter()}
    multitask = config["n_tasks"] > 1
    session_seed = int(weights.seed_words(seed, 1)[0] % (2 ** 31))
    sess = program.session(config, traffic, sources, names,
                           seed=session_seed)
    marks["session"] = time.perf_counter()
    params = weights.init_params(config, seed)
    program.set_params(sess, params)
    del params
    feed_src = CountingFeed(sess.batcher)
    sess.batcher = feed_src
    n_check = int(traffic["check_steps"])
    ahead = {}
    la = frozen_copy(feed_src.batcher, sources)
    worker = threading.Thread(target=lambda: ahead.update(lookahead(
        la, int(traffic["lookahead_steps"]))), name="lookahead")
    worker.start()

    step = sess.compiled_step
    if fault is not None:
        step = fault(step)
    feed = sess._batches()
    state = sess.state

    @jax.jit
    def norms(tree):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                          for x in jax.tree_util.tree_leaves(tree)])

    # the first steps, as the window takes them, read back one by one
    checked, losses = [], []
    for i in range(n_check):
        b = feed()
        checked.append(b)
        state, out = step(state, b)
        losses.append(float(out.loss))
        if i == 0:
            grad_norms = np.asarray(norms(state.opt_state.m)) / (1.0 - B1)
    start = weights.init_params(config, seed)
    change_norms = np.asarray(norms(jax.tree_util.tree_map(
        lambda a, b: a - b, state.params, start)))
    del start
    host_batches = jax.device_get(checked)
    del checked
    marks["checked_steps"] = time.perf_counter()
    worker.join()
    marks["lookahead"] = time.perf_counter()
    warmed = {feed_src.rows[i]["shape"] for i in range(n_check)}
    for shape, example in ahead.items():
        if shape not in warmed:
            scratch = jax.tree_util.tree_map(jnp.copy, state)
            jax.block_until_ready(step(scratch, jax.device_put(example)))
            warmed.add(shape)
    del ahead
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t_start
    marks["warm_up"] = time.perf_counter()
    prev, parts = t_start, []
    for k, t in marks.items():
        parts.append(f"{k} {t - prev:.2f}")
        prev = t
    log(f"train: set-up {setup_s:.2f} s ({', '.join(parts)} s), shapes "
        f"warmed {sorted(warmed)}")

    # the window
    limit = float(traffic["trace_seconds"]) if trace else float(seconds)
    span = harness.spans(trace)
    trace_dir = os.path.join(harness.ROOT, ".perfbench_trace")
    if trace:
        harness.start_trace(trace_dir)
    n, wait, pending = 0, 0.0, collections.deque()
    with span("window"):
        t0 = time.perf_counter()
        while True:
            a = time.perf_counter()
            with span("next_batch"):
                b = feed()
            wait += time.perf_counter() - a
            with span("step_dispatch"):
                state, out = step(state, b)
            n += 1
            pending.append(out.loss)
            if len(pending) > IN_FLIGHT:
                with span("step_wait"):
                    pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= limit:
                break
        with span("final_sync"):
            jax.block_until_ready((state, out.loss))
        t1 = time.perf_counter()
    tr = None
    if trace:
        jax.profiler.stop_trace()
        tr = devtrace.read_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    # batches the window consumed: the prefetcher drew them in order
    counted = feed_src.rows[n_check:n_check + n]
    unseen = sum(1 for c in counted if c["shape"] not in warmed)
    last_loss = float(out.loss)
    window_s = t1 - t0
    log(f"train: {n} steps in {window_s:.4f} s, last loss {last_loss!r}, "
        f"batches of unwarmed shape {unseen}")
    memory = harness.memory_peak(devices)

    # free the program, then follow the first steps with the reference
    sess.close()
    del state, out, b, sess, step, feed
    gc.collect()
    keys = atoms.row_keys(sources)
    bad = rows_bad(host_batches, keys, multitask)
    w = source_weights(sources, traffic["mixing_temperature"],
                       config["n_tasks"])
    hp = {k: traffic[k] for k in ("lr", "warmup", "schedule_steps",
                                  "weight_decay")}
    t_ref = time.perf_counter()
    ref = harness.reference(config)
    readings = common.train_readings(
        ref.trunk, ref.branch, weights.init_params(config, seed),
        [jax.device_put(b) for b in host_batches], w, hp, config)
    log(f"train: reference {time.perf_counter() - t_ref:.2f} s; losses "
        f"program {losses!r} reference {readings['loss']!r}")
    ref_grad = np.asarray(readings["grad_norms"])
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: left out of the change
    still = ref_grad < 1e-3 * np.median(ref_grad)
    checks = [
        {"name": "loss_gap", "value": float(max(
            abs(p - r) / abs(r) for p, r in zip(losses, readings["loss"])))},
        {"name": "grad_norm_gap",
         "value": leaf_gap(grad_norms, ref_grad)},
        {"name": "change_norm_gap",
         "value": leaf_gap(change_norms, readings["change_norms"],
                           skip=still)},
        {"name": "batch_rows_bad", "value": float(bad)},
    ]
    for c in checks:
        c["limit"] = float(limits[c["name"]])
    structures = sum(c["structures"] for c in counted)
    return {
        "kind": "train", "setup_s": setup_s, "window_s": window_s,
        "steps": n, "structures": structures,
        "atoms": sum(c["atoms"] for c in counted),
        "edges": sum(c["edges"] for c in counted),
        "edge_slots": sum(c["edge_slots"] for c in counted),
        "per_step": counted, "input_wait_s": wait, "config": config,
        "chips": len(devices), "device_kind": devices[0].device_kind,
        "unwarmed_batches": unseen, "last_loss": last_loss,
        "memory_peak_bytes": memory, "trace": tr, "checks": checks,
        "attempted": n, "failed": 0,
    }
