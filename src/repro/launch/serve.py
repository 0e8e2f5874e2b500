"""Serving launcher: batched generation with (optionally int4) weights.

    PYTHONPATH=src python -m repro.launch.serve --arch opt-proxy --smoke \
        --prompt-len 32 --batch 4 serve.max_new_tokens=16

``serve.scheduler=continuous`` routes the same prompts through the
continuous-batching engine (serving/scheduler.py) instead of the static
batch; ``--pack-rtn`` RTN-packs the (init or loaded) weights to int4 so
the quantized decode hot path runs without a quantize-pipeline artifact.

``--params`` artifacts load through the integrity-checked
``distributed.checkpoint.load_artifact`` path (sha256 sidecar manifest
from ``launch.quantize``): a corrupt artifact is a typed
``ArtifactIntegrityError``, never a silent load.
``serve.supervise=true`` wraps the continuous engine in the crash-
recovering supervisor (serving/supervisor.py, docs/SERVING.md §Crash
recovery).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.config import apply_overrides, parse_overrides
from repro.configs.registry import get_config
from repro.core import faults
from repro.data import MarkovLM
from repro.distributed.checkpoint import load_artifact
from repro.launch.compile_cache import setup_compile_cache
from repro.models import transformer as T
from repro.serving.engine import generate
from repro.serving.scheduler import ContinuousEngine
from repro.serving.supervisor import SupervisedEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--params", default=None,
                    help="pickled packed params from launch.quantize")
    ap.add_argument("--pack-rtn", action="store_true",
                    help="RTN-pack weights to int4 QuantizedTensor before "
                         "serving (no quantize run needed)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("overrides", nargs="*")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, smoke=args.smoke)
    apply_overrides(cfg, parse_overrides(args.overrides))
    mc = cfg.model
    setup_compile_cache()
    faults.install_from_config(cfg)
    if cfg.faults.arm:
        print(f"[serve] fault plane armed: {cfg.faults.arm}")

    key = jax.random.PRNGKey(0)
    if args.params:
        params = load_artifact(args.params)
        print(f"[serve] loaded int4 params from {args.params} "
              "(integrity-checked)")
    else:
        params = (T.init_encdec_params(mc, key) if mc.is_encoder_decoder
                  else T.init_params(mc, key))
    if args.pack_rtn:
        from repro.core.pipeline import pack_for_serving
        params = pack_for_serving(cfg, params)
        print(f"[serve] RTN-packed weights to int4 "
              f"(w4a16_impl={cfg.serve.w4a16_impl})")

    data = MarkovLM(mc.vocab_size, seed=3)
    batch = data.batch(args.batch, args.prompt_len)
    if mc.is_encoder_decoder:
        batch["frames"] = jax.random.normal(
            key, (args.batch, mc.encoder_seq_len, mc.d_model), jnp.float32)
    elif mc.frontend in ("vision", "audio") and mc.frontend_tokens:
        batch["embeds"] = jax.random.normal(
            key, (args.batch, min(mc.frontend_tokens, 8), mc.d_model),
            jnp.float32)

    t0 = time.perf_counter()
    if cfg.serve.scheduler == "continuous":
        n_front = batch["embeds"].shape[1] if "embeds" in batch else 0
        cap = args.prompt_len + n_front + cfg.serve.max_new_tokens + 1
        if cfg.serve.supervise:
            # crash-recovering supervisor; a --params path is handed down
            # so a rebuild re-reads the artifact through the integrity
            # check instead of trusting a possibly-poisoned in-memory tree
            eng = SupervisedEngine(cfg, params, max_len=cap,
                                   params_path=args.params or None)
            print("[serve] supervised engine "
                  f"(max_restarts={cfg.serve.max_restarts}, "
                  f"step_timeout_s={cfg.serve.step_timeout_s})")
        else:
            eng = ContinuousEngine(cfg, params, max_len=cap)
        rids = []
        for i in range(args.batch):
            one = {k: v[i:i + 1] for k, v in batch.items()}
            rids.append(eng.submit(one))
        done = eng.run()
        seqs = [done[r].tokens for r in rids]
        toks = int(sum(len(s) for s in seqs))
        bad = {r: done[r].status for r in rids if done[r].status != "ok"}
        if bad:
            print(f"[serve] non-ok requests: {bad}")
        if any(done[r].status != "ok" for r in rids) or \
                any(v for v in eng.stats.values()):
            print(f"[serve] engine stats: {eng.engine_stats()}")
    else:
        res = generate(cfg, params, batch)
        seqs = [res.tokens[i] for i in range(args.batch)]
        toks = int(res.tokens.size)
    dt = time.perf_counter() - t0
    print(f"[serve] scheduler={cfg.serve.scheduler}: {toks} tokens "
          f"in {dt:.2f}s ({toks/dt:.1f} tok/s incl. compile)")
    for i in range(min(args.batch, 4)):
        print(f"  seq{i}: {list(map(int, seqs[i]))}")


if __name__ == "__main__":
    main()
