"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch opt-proxy \
        train.steps=200 train.global_batch_size=16 [--smoke] [--mesh d,m]

Uses the smoke (reduced) config by default on CPU; the full config with the
production mesh on real hardware. Checkpoints land in train.ckpt_dir and
restarts resume automatically (including the data-stream position).
"""
from __future__ import annotations

import argparse

from repro.config import apply_overrides, parse_overrides
from repro.configs.registry import get_config
from repro.data import MarkovLM
from repro.launch.mesh import make_host_mesh
from repro.training.trainer import train


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-scale)")
    ap.add_argument("--mesh", default=None,
                    help="data,model (defaults to single device)")
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    apply_overrides(cfg, parse_overrides(args.overrides))

    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_host_mesh(data=d, model=m)

    data = MarkovLM(cfg.model.vocab_size, seed=cfg.train.seed)
    out = train(cfg, data, mesh=mesh)
    final = out["history"][-1] if out["history"] else {}
    print(f"done: step={final.get('step')} loss={final.get('loss'):.4f}")


if __name__ == "__main__":
    main()
