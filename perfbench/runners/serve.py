"""Serving runner: an open-loop stream through the program's ``ServeSession``.

Set-up makes the sources, the weights from ``--seed`` and the session with
the traffic's bucket grid and release knobs, and compiles the forward for
every bucket the stream's requests fall into (and no other). The window
sends ``rate * seconds`` requests on the seeded schedule of
``perfbench/openloop.py``; each is timed from its due time to its result.
After the window closes the harness waits for every request (up to a
minute), frees the session, and compares a seeded sample of the answers,
the largest structure among them, with the plain reference.
"""
from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

from perfbench import atoms, devtrace, harness, openloop, program, weights
from perfbench.reference import common

WAIT_AFTER_CLOSE_S = 60.0


def answer_gaps(answers, ref_e, ref_f, n_atoms):
    """Worst sampled request's energy gap and force gap, each over the
    larger of that request's reference magnitude and the sample's median
    (RMS over real atom components for forces)."""
    e = np.asarray([a["energy"] for a in answers], float)
    ref_e = np.asarray(ref_e, float)
    e_scale = np.maximum(np.abs(ref_e), np.median(np.abs(ref_e)))
    f_gap, f_rms = [], []
    for a, rf, n in zip(answers, ref_f, n_atoms):
        rf = np.asarray(rf, float)[:n]
        f_rms.append(np.sqrt(np.mean(rf ** 2)) if n else 0.0)
        got = np.asarray(a["forces"], float)
        f_gap.append(np.max(np.abs(got - rf)) if got.shape == rf.shape
                     else np.inf)
    f_rms = np.asarray(f_rms)
    f_scale = np.maximum(f_rms, np.median(f_rms))
    return (float(np.max(np.abs(e - ref_e) / e_scale)),
            float(np.max(np.asarray(f_gap) / f_scale)))


def run(*, config, traffic, limits, seed, seconds, trace, devices, t_start,
        log=harness.log, fault=None):
    import jax

    names, sources = atoms.generate(harness.sources_spec(traffic))
    t_data = time.perf_counter()
    span_s = float(traffic["trace_seconds"]) if trace else float(seconds)
    rate = float(traffic["rate_per_s"])
    due = openloop.schedule(rate, span_s, int(traffic["arrival_seed"]),
                            int(weights.seed_words(seed, 2)[0]))
    fixed = openloop.request_pool(sources, len(due),
                                  int(traffic["arrival_seed"]))
    order = np.random.default_rng(weights.seed_words(seed, 3)).permutation(
        len(fixed))
    pool = [fixed[i] for i in order]

    params = weights.init_params(config, seed)
    srv, bspec = program.serve_session(config, traffic, params, sources)
    del params
    used = sorted({bspec.bucket_for(int(sources[t]["node_mask"][r].sum()),
                                    int(sources[t]["edge_mask"][r].sum()))
                   for t, r in pool})
    t_session = time.perf_counter()
    srv.warmup(buckets=used)
    if fault is not None:
        fault(srv)
    before = dict(srv.stats()["counters"])
    setup_s = time.perf_counter() - t_start
    log(f"serve: set-up {setup_s:.2f} s (imports and data "
        f"{t_data - t_start:.2f}, "
        f"session {t_session - t_data:.2f}, warm-up "
        f"{t_start + setup_s - t_session:.2f} s), {len(pool)} requests at {rate} /s "
        f"over {span_s} s, buckets warmed {used}")

    span = harness.spans(trace)
    trace_dir = os.path.join(harness.ROOT, ".perfbench_trace")
    if trace:
        harness.start_trace(trace_dir)
    with span("window"):
        rec = openloop.drive(srv.submit, sources, pool, due,
                             span=span if trace else None)
        t_close = rec.due[0] + span_s
        with span("result_wait"):
            rec.all_done.wait(timeout=max(0.0, t_close - time.perf_counter())
                              + WAIT_AFTER_CLOSE_S)
    tr = None
    if trace:
        jax.profiler.stop_trace()
        tr = devtrace.read_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    after = dict(srv.stats()["counters"])
    qw = srv.stats()["latency"]["queue_wait"]
    memory = harness.memory_peak(devices)
    late = rec.sent - rec.due
    lat = rec.latency_ms()
    log(f"serve: generator lateness ms p50 {1e3 * float(np.median(late))!r}"
        f" p99 {1e3 * float(np.percentile(late, 99))!r} max "
        f"{1e3 * float(late.max())!r}")
    answered = int(np.sum(~rec.failed & (rec.done <= t_close)))
    unanswered = int(np.sum(rec.failed | np.isnan(rec.done)))
    log(f"serve: {len(pool)} due, {answered} answered before the close, "
        f"{unanswered} failed or never answered; latency ms p50 "
        f"{float(np.percentile(lat, 50))!r} p95 "
        f"{float(np.percentile(lat, 95))!r}")

    # a seeded sample of the answers, with the largest structure in it
    done = [i for i in range(len(pool)) if not rec.failed[i]
            and not np.isnan(rec.done[i])]
    size = [int(sources[pool[i][0]]["node_mask"][pool[i][1]].sum())
            for i in done]
    k = min(int(traffic["check_requests"]), len(done))
    rng = np.random.default_rng(weights.seed_words(seed, 4))
    pick = set(rng.choice(len(done), size=k, replace=False).tolist()) \
        if done else set()
    if done:
        pick.add(int(np.argmax(size)))
    sample = [done[j] for j in sorted(pick)]
    answers = [rec.futures[i].result() for i in sample]
    srv.close()
    del srv, rec.futures
    gc.collect()
    a_cap = max(used)[0] if used else 1
    e_cap = max(e for _, e in used) if used else 1
    rows = {key: np.stack([sources[pool[i][0]][key][pool[i][1]]
                           for i in sample]) for key in atoms.SAMPLE_KEYS} \
        if sample else {}
    checks = [{"name": "requests_unanswered", "value": float(unanswered)}]
    if sample:
        for key in ("species", "pos", "node_mask"):
            rows[key] = rows[key][:, :a_cap]
        for key in ("edge_src", "edge_dst", "edge_mask"):
            rows[key] = rows[key][:, :e_cap]
        ref = harness.reference(config)
        ref_e, ref_f = common.serve_readings(
            ref.trunk, ref.branch, weights.init_params(config, seed), rows,
            [pool[i][0] for i in sample], config)
        n_atoms = [int(sources[pool[i][0]]["node_mask"][pool[i][1]].sum())
                   for i in sample]
        e_gap, f_gap = answer_gaps(answers, ref_e, ref_f, n_atoms)
    else:
        e_gap = f_gap = float("inf")
    checks += [{"name": "energy_gap", "value": e_gap},
               {"name": "force_gap", "value": f_gap}]
    for c in checks:
        c["limit"] = float(limits[c["name"]])
    return {
        "kind": "serve", "setup_s": setup_s, "window_s": span_s,
        "latency_ms": lat, "answered_in_window": answered,
        "batch_real": after["batch_real"] - before["batch_real"],
        "batch_slots": after["batch_slots"] - before["batch_slots"],
        "queue_wait_p95_ms": qw["p95_ms"] if qw["count"] else None,
        "generator_late_ms": 1e3 * late, "chips": len(devices),
        "device_kind": devices[0].device_kind, "memory_peak_bytes": memory,
        "trace": tr, "checks": checks, "attempted": len(pool),
        "failed": unanswered,
    }
