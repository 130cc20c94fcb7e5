"""Runnable trainer CLI — a thin argparse front-end over ``repro.engine``.

  # the paper's GFM at its published widths (H=866, 4 layers, 5 branches):
  PYTHONPATH=src python -m repro.launch.train --mode gfm --no-smoke --steps 200

  # the same model cut down to smoke scale (the default):
  PYTHONPATH=src python -m repro.launch.train --mode gfm --steps 20

  # any assigned LM arch at smoke scale:
  PYTHONPATH=src python -m repro.launch.train --mode lm --arch qwen1.5-0.5b --steps 50

  # multi-task LM (the paper's technique on an LLM trunk):
  PYTHONPATH=src python -m repro.launch.train --mode lm-mtl --arch qwen1.5-0.5b
"""
from __future__ import annotations

import argparse

from repro import configs
from repro.data.lm_data import make_lm_sources
from repro.data.synthetic_atoms import generate_all
from repro.engine import Session, SessionConfig
from repro.launch import compile_cache


def arch_for(args):
    """The ArchConfig a run trains: the published config with ``--no-smoke``,
    the arch's smoke cut otherwise."""
    name = "hydragnn-gfm" if args.mode == "gfm" else args.arch
    return configs.get_smoke(name) if args.smoke else configs.get(name)


def session_for(args) -> Session:
    cfg = arch_for(args)
    if args.mode == "gfm":
        data = list(generate_all(args.samples, max_atoms=cfg.max_atoms,
                                 max_edges=cfg.max_edges).items())[:cfg.n_tasks]
        sources = [dict(species=s.species, pos=s.pos, edge_src=s.edge_src,
                        edge_dst=s.edge_dst, node_mask=s.node_mask,
                        edge_mask=s.edge_mask, energy=s.energy,
                        forces=s.forces) for _, s in data]
        # paper: AdamW, lr 1e-3, warmup-cosine, early stopping
        scfg = SessionConfig(model="gfm-mtl", arch=cfg, steps=args.steps,
                             batch_per_task=args.batch, lr=args.lr,
                             warmup=20, accum=args.accum, seed=args.seed,
                             log_every=args.log_every,
                             eval_every=args.log_every, patience=20,
                             ckpt_path=args.ckpt)
        return Session.from_config(scfg, sources=sources,
                                   task_names=[k for k, _ in data])

    if args.mode == "lm-mtl":
        cfg = cfg.replace(n_tasks=args.tasks)
        sources = make_lm_sources(cfg.n_tasks, 64, args.seq, cfg.vocab)
        scfg = SessionConfig(model="lm-mtl", arch=cfg, steps=args.steps,
                             batch_per_task=args.batch, lr=args.lr,
                             accum=args.accum, seed=args.seed,
                             log_every=args.log_every, ckpt_path=args.ckpt)
        return Session.from_config(scfg, sources=sources)

    source = make_lm_sources(1, 256, args.seq, cfg.vocab)[0]
    scfg = SessionConfig(model="lm", arch=cfg, steps=args.steps,
                         batch_per_task=args.batch, lr=args.lr,
                         accum=args.accum, seed=args.seed,
                         log_every=args.log_every, ckpt_path=args.ckpt)
    return Session.from_config(scfg, sources=source)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mode", default="gfm", choices=["gfm", "lm", "lm-mtl"])
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--tasks", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--samples", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the arch's smoke cut (--no-smoke: the "
                         "published config)")
    ap.add_argument("--ckpt", default=None)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()
    with session_for(args) as session:
        result = session.run()
    return result.final_loss


if __name__ == "__main__":
    main()
