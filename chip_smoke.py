#!/usr/bin/env python3
"""Bring-up smoke test: quantize → pack → serve on one TPU, end to end.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: sharded-quantize parity

Runs opt-proxy at its full widths (the paper's OPT family at OPT-125m's
published widths: 12 layers, d_model 768, 12 heads, d_ff 3072, vocab
50304) with random weights from ``PRNGKey(0)``, through the entry points a
user calls:

  (a) device: platform, device kind and count. Anything but a TPU exits
      nonzero before any work.
  (b) kernel parity: each of the five main-path Pallas kernels at
      opt-proxy's shapes, dispatched with the dispatcher's own tiles, must
      lower to a ``tpu_custom_call`` and match its XLA reference within
      the interpret-mode tests' tolerances, both sides at ``highest``
      matmul precision.
  (c) quantize: ``repro.launch.quantize.main`` with the default
      calibration (8 × 16 × 512 tokens); prints wall time, the report
      summary, the counted kernel budget fallbacks and peak device memory.
  (d) serve: the artifact, loaded through its integrity check, answers 8
      requests (128-token prompts, 32 new tokens) on ``ContinuousEngine``
      with ``serve.kv_cache=int8``. Every request must end ``ok`` with no
      kernel degradation, the compiled decode step must hold both serving
      kernels, and its first-decode-step logits must match the same
      artifact served with ``w4a16_impl=xla kv_impl=xla`` within
      ``LOGIT_REL_TOL``. Greedy-token agreement is printed.

``--chips 4`` runs only the sharded path: quantize with
``quant.mesh=2x2`` and with ``quant.mesh=off``, in this process, and
compares the two artifacts bit for bit (DESIGN.md §2.6 claims the sharded
executor is exact). It passes when at most ``SHARDED_MISMATCH_TOL`` of
the elements differ and the mesh spread shows stage-1 groups that really
ran on four devices.

One process, no children: the chip belongs to one process at a time. The
compile cache follows ``repro.launch.compile_cache``. The last stdout line
is the JSON verdict, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "artifacts", "chip_smoke")
ARCH = "opt-proxy"

# relative L2 distance allowed between the pallas and XLA serving paths'
# first-decode-step logits. Both paths serve at the TPU's default matmul
# precision, where an f32 dot rounds its operands to bf16 once, but they
# round at different points (XLA's fused dequantize, the kernels' per-tile
# dots): ~2^-9 relative differences enter every matmul, compound over 12
# layers and are re-quantized into the int8 KV cache. A v5e measured ~1e-2.
LOGIT_REL_TOL = 5e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# (b) kernel parity
# ---------------------------------------------------------------------------

def _close(name, got, want, *, rtol=0.0, atol=0.0):
    """(ok, detail) for an allclose check, with the worst violation."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return False, f"{name}: shape {got.shape} != {want.shape}"
    # equal infinities (masked loss-history rounds) count as a match
    err = np.where(got == want, 0.0, np.abs(got - want))
    bound = atol + rtol * np.abs(np.where(np.isfinite(want), want, 0.0))
    bad = int((~(err <= bound)).sum())
    detail = (f"{name}: max|d|={err.max():.3g} "
              f"({bad}/{err.size} over atol={atol:g} rtol={rtol:g})")
    return bad == 0, detail


def _kernel_cases(*, tokens, d_model, d_ff, lanes, prefill, kv_heads,
                  head_dim, cap, rpiq_rows):
    """(name, pallas_fn, ref_fn, args, compare) for every main-path kernel
    at the given model widths; the pallas side goes through the
    dispatcher (``impl="pallas"``) so it runs the tiles a user gets."""
    import jax
    import jax.numpy as jnp
    from repro.core import hessian as hess
    from repro.core.quant import pack_quantized
    from repro.core.rpiq import _block_curvature_inv
    from repro.kernels import kv_codec, ops

    keys = iter(jax.random.split(jax.random.PRNGKey(0), 64))

    def normal(shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    cases = []
    tol_h = dict(rtol=1e-4, atol=1e-3)               # test_kernels.py
    for d in (d_model, d_ff):
        x = normal((tokens, d))
        cases.append((f"hessian_accum x({tokens},{d})",
                      functools.partial(ops.hessian_accum, impl="pallas"),
                      functools.partial(ops.hessian_accum, impl="xla"),
                      (x,), lambda n, g, w: [_close(n, g, w, **tol_h)]))

    for k, n in ((d_model, d_ff), (d_ff, d_model)):
        qt = pack_quantized(normal((n, k), 0.05), 4, 128)
        for m in (lanes, prefill):
            cases.append((
                f"w4a16_matmul m{m} k{k} n{n}",
                functools.partial(ops.w4a16_matmul, group_size=128,
                                  impl="pallas"),
                functools.partial(ops.w4a16_matmul, group_size=128,
                                  impl="xla"),
                (normal((m, k)), qt.packed, qt.scales, qt.zeros),
                lambda n_, g, w: [_close(n_, g, w, rtol=1e-4, atol=1e-3)]))

    blk = kv_codec.default_kv_block(head_dim)
    kc, ks = kv_codec.enc_int8_blocks(normal((lanes, cap, kv_heads,
                                              head_dim)), blk)
    vc, vs = kv_codec.enc_int8_blocks(normal((lanes, cap, kv_heads,
                                              head_dim)), blk)
    q = normal((lanes, kv_heads, 1, head_dim), head_dim ** -0.5)
    # lane i has decoded up to position cap - 1 - i: the tail is unwritten
    last = cap - 1 - jnp.arange(lanes)[:, None]
    kpos = jnp.where(jnp.arange(cap)[None] <= last,
                     jnp.arange(cap)[None], -1).astype(jnp.int32)
    cases.append((f"int8_kv_attention B{lanes} S{cap} KV{kv_heads} "
                  f"hd{head_dim}",
                  functools.partial(ops.int8_kv_attention, kv_block=blk,
                                    impl="pallas"),
                  functools.partial(ops.int8_kv_attention, kv_block=blk,
                                    impl="xla"),
                  (q, kc, ks, vc, vs, kpos),
                  lambda n, g, w: [_close(n, g, w, rtol=1e-5, atol=1e-5)]))

    # stage 1 + stage 2 on a real calibration Hessian; stage 1 also at the
    # d_ff input of the down projection, where U streams through VMEM
    x = normal((tokens, d_model))
    st = hess.accumulate(hess.init_hessian(d_model), x)
    hd = hess.damped(st, 0.01)
    u = hess.cholesky_inverse_upper(hd)
    u_ff = hess.cholesky_inverse_upper(hess.damped(
        hess.accumulate(hess.init_hessian(d_ff), normal((tokens, d_ff))),
        0.01))

    def gptq_cmp(n, g, w):                           # test_gptq_kernel.py
        return [_close(f"{n} w_q", g[0], w[0], atol=1e-6),
                _close(f"{n} scales", g[1], w[1], atol=1e-6),
                _close(f"{n} zeros", g[2], w[2], atol=1e-6),
                _close(f"{n} err", g[3], w[3], rtol=1e-4)]

    for b, out, inp, u_in in ((3, d_model, d_model, u),
                              (1, d_ff, d_model, u),
                              (1, d_model, d_ff, u_ff)):
        w = normal((b, out, inp), 0.05)
        ub = jnp.broadcast_to(u_in, (b, inp, inp))
        cases.append((f"gptq_block B{b} out{out} in{inp}",
                      functools.partial(ops.gptq_block, impl="pallas"),
                      functools.partial(ops.gptq_block, impl="xla"),
                      (w, ub), gptq_cmp))

    w = normal((d_model, d_model), 0.05)
    w1, s1, z1, _ = ops.gptq_block(w, u, impl="xla")
    x_last = x[-rpiq_rows:]
    hinv = _block_curvature_inv(x_last, hd, st.count, None, block_size=128,
                                exact_gram=False)

    def rpiq_cmp(n, g, w_):                          # test_rpiq_kernel.py
        return [_close(f"{n} w_q", g[0], w_[0], atol=1e-6),
                _close(f"{n} loss_history", g[2], w_[2], rtol=1e-6),
                _close(f"{n} proj_loss", g[3], w_[3], rtol=1e-6),
                _close(f"{n} iters_run", g[4], w_[4])]

    cases.append((f"rpiq_block out{d_model} in{d_model} n{rpiq_rows}",
                  functools.partial(ops.rpiq_block, impl="pallas"),
                  functools.partial(ops.rpiq_block, impl="xla"),
                  (w1, w, x_last, hinv, s1, z1), rpiq_cmp))
    return cases


def phase_kernels(**widths) -> bool:
    import jax
    ok_all = True
    for name, fn_p, fn_r, args, compare in _kernel_cases(**widths):
        # both sides at f32 matmul precision, as in interpret mode: at the
        # TPU default an f32 dot is one bf16 pass, and Mosaic and XLA would
        # then differ by bf16 rounding, not by what the kernel computes
        with jax.default_matmul_precision("highest"):
            compiled = jax.jit(fn_p).lower(*args).compile()
            got = jax.block_until_ready(compiled(*args))
            want = jax.block_until_ready(jax.jit(fn_r)(*args))
        n_calls = compiled.as_text().count("tpu_custom_call")
        checks = compare(name, got, want)
        ok = n_calls > 0 and all(c for c, _ in checks)
        ok_all &= ok
        log(f"(b) {'ok  ' if ok else 'FAIL'} {name}: "
            f"tpu_custom_call x{n_calls}")
        for _, detail in checks:
            log(f"      {detail}")
    return ok_all


# ---------------------------------------------------------------------------
# (c) quantize
# ---------------------------------------------------------------------------

def _peak_bytes():
    stats = __import__("jax").devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def quantize(out_dir: str, overrides=(), smoke: bool = False):
    """launch.quantize.main into ``out_dir``; returns (artifact path,
    report)."""
    from repro.launch import quantize as launch_quantize
    argv = ["--arch", ARCH, "--out", out_dir,
            f"train.ckpt_dir={os.path.join(out_dir, 'no_train_ckpt')}",
            *overrides] + (["--smoke"] if smoke else [])
    t0 = time.perf_counter()
    report = launch_quantize.main(argv)
    wall = time.perf_counter() - t0
    name = "opt-proxy-smoke" if smoke else "opt-proxy"
    log(f"(c) quantize {' '.join(overrides) or '(defaults)'}: wall "
        f"{wall:.3f}s incl. compile; kernel_fallbacks="
        f"{report.kernel_fallbacks}; mesh_spread={report.mesh_spread}; "
        f"peak_bytes_in_use={_peak_bytes()}")
    return os.path.join(out_dir, f"{name}.params.pkl"), report


# ---------------------------------------------------------------------------
# (d) serve
# ---------------------------------------------------------------------------

def _serve_cfg(smoke: bool, **serve_kw):
    from repro.configs.registry import get_config
    cfg = get_config(ARCH, smoke=smoke)
    return dataclasses.replace(cfg, serve=dataclasses.replace(
        cfg.serve, kv_cache="int8", scheduler="continuous", **serve_kw))


def _first_decode_logits(cfg, params, prompts, first, max_len):
    """Prefill ``prompts`` and run one decode step from token ``first``
    under ``cfg``'s kernel backends; returns (logits, decode-step text)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.serving import engine as E
    with ops.w4a16_default_impl(cfg.serve.w4a16_impl), \
            ops.kv_attn_default_impl(cfg.serve.kv_impl):
        _, caches = jax.jit(functools.partial(E.prefill, cfg),
                            static_argnums=2)(params, prompts, max_len)
        pos = jnp.full((first.shape[0],), prompts["tokens"].shape[1],
                       jnp.int32)
        step = jax.jit(functools.partial(E.serve_step, cfg))
        text = step.lower(params, first, pos, caches).as_text()
        logits, _ = step(params, first, pos, caches)
    return logits, text


def phase_serve(path: str, *, smoke: bool = False, requests: int = 8,
                prompt_len: int = 128, new_tokens: int = 32) -> bool:
    import jax.numpy as jnp
    import numpy as np
    from repro.data import MarkovLM
    from repro.distributed.checkpoint import load_artifact
    from repro.serving.scheduler import ContinuousEngine

    params = load_artifact(path)
    cfg_k = _serve_cfg(smoke, max_batch=requests, max_new_tokens=new_tokens)
    cfg_x = _serve_cfg(smoke, max_batch=requests, max_new_tokens=new_tokens,
                       w4a16_impl="xla", kv_impl="xla")
    prompts = MarkovLM(cfg_k.model.vocab_size, seed=3).batch(requests,
                                                             prompt_len)
    cap = prompt_len + new_tokens + 1
    ok = True
    tokens = {}
    for tag, cfg in (("kernels", cfg_k), ("xla", cfg_x)):
        eng = ContinuousEngine(cfg, params, max_len=cap)
        rids = [eng.submit({"tokens": prompts["tokens"][i:i + 1]})
                for i in range(requests)]
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        stats = eng.engine_stats()
        statuses = [done[r].status for r in rids]
        tokens[tag] = [np.asarray(done[r].tokens) for r in rids]
        n_tok = sum(len(t) for t in tokens[tag])
        good = (all(s == "ok" for s in statuses)
                and stats["kernel_degradations"] == 0)
        ok &= good
        log(f"(d) {'ok  ' if good else 'FAIL'} serve [{tag}]: {requests} "
            f"requests, {n_tok} tokens in {wall:.3f}s incl. compile; "
            f"statuses={sorted(set(statuses))} "
            f"kernel_degradations={stats['kernel_degradations']} "
            f"w4a16_impl={stats['w4a16_impl']} kv_impl={stats['kv_impl']} "
            f"kernel_fallbacks={stats['kernel_fallbacks']}")

    first = jnp.asarray([int(t[0]) for t in tokens["xla"]], jnp.int32)
    lg_k, text_k = _first_decode_logits(cfg_k, params, prompts, first, cap)
    lg_x, _ = _first_decode_logits(cfg_x, params, prompts, first, cap)
    lg_k, lg_x = (np.asarray(a, np.float64) for a in (lg_k, lg_x))
    rel = float(np.linalg.norm(lg_k - lg_x) / np.linalg.norm(lg_x))
    kernels_in = {k: k in text_k for k in ("_w4a16_kernel",
                                           "_kv_attn_kernel")}
    good = (rel <= LOGIT_REL_TOL and all(kernels_in.values())
            and bool(np.isfinite(lg_k).all()))
    ok &= good
    log(f"(d) {'ok  ' if good else 'FAIL'} first-decode-step logits: "
        f"relative L2 distance {rel:.3g} (tolerance {LOGIT_REL_TOL:g}), "
        f"max|d|={np.abs(lg_k - lg_x).max():.3g}; decode step holds "
        f"{kernels_in}")
    pairs = list(zip(tokens["kernels"], tokens["xla"]))
    same = sum(int(np.sum(a[:len(b)] == b[:len(a)])) for a, b in pairs)
    total = sum(max(len(a), len(b)) for a, b in pairs)
    first_same = sum(int(a[0] == b[0]) for a, b in pairs)
    log(f"(d) greedy-token agreement kernels vs xla: {same}/{total} "
        f"positions, first token {first_same}/{requests}")
    return ok


# ---------------------------------------------------------------------------
# --chips 4: sharded quantize parity
# ---------------------------------------------------------------------------

# the bound tests/_distributed_checks.check_sharded_plan_parity holds the
# sharded executor to: f32 rounding that differs with the slab shape may
# flip the odd grid cell
SHARDED_MISMATCH_TOL = 1e-3

SHARDED_RUNS = (("2x2", ("quant.mesh=2x2",)), ("off", ("quant.mesh=off",)))


def _bit_mismatch(a, b):
    """Bitwise comparison of two artifacts' ``(path, leaf)`` lists:
    ``(differing elements, elements, differing leaves, differing elements
    per layer of the stacked blocks, {leaf: differing elements} in the
    first layer that differs)``, or None when their structure, shapes or
    dtypes differ. The per-layer split shows where in the walk two runs
    part: a difference in one layer's codes changes the next layer's
    calibration."""
    import numpy as np
    if [p for p, _ in a] != [p for p, _ in b]:
        return None
    diff = total = leaves = 0
    by_layer = {}                       # stacked-block leaf -> per layer
    for (path, x), (_, y) in zip(a, b):
        x, y = np.ascontiguousarray(x), np.ascontiguousarray(y)
        if x.shape != y.shape or x.dtype != y.dtype:
            return None
        bits = np.dtype(f"u{x.dtype.itemsize}")
        ne = x.view(bits) != y.view(bits)
        n = int(ne.sum())
        diff, total, leaves = diff + n, total + x.size, leaves + (n > 0)
        if "'blocks'" in path and x.ndim >= 2:      # (layers, ...) stacks
            by_layer[path] = ne.reshape(x.shape[0], -1).sum(axis=1)
    per_layer = [int(c) for c in sum(by_layer.values())] if by_layer else []
    first = next((i for i, c in enumerate(per_layer) if c), None)
    first_leaves = {} if first is None else {
        p: int(c[first]) for p, c in by_layer.items() if c[first]}
    return diff, total, leaves, per_layer, first_leaves


def phase_sharded(smoke: bool = False) -> bool:
    import jax
    from repro.distributed.checkpoint import load_artifact
    leaves, reports = {}, {}
    for label, overrides in SHARDED_RUNS:
        path, reports[label] = quantize(
            os.path.join(OUT, "mesh_" + label.replace(" ", "_")),
            list(overrides), smoke=smoke)
        leaves[label] = [
            (jax.tree_util.keystr(p), x) for p, x in
            jax.tree_util.tree_flatten_with_path(load_artifact(path))[0]]
    spread = reports["2x2"].mesh_spread
    n_shards = spread.get("stage1_shards=4", 0)
    ok = n_shards > 0
    log(f"(4) {'ok  ' if ok else 'FAIL'} quant.mesh=2x2: {n_shards} "
        f"stage-1 groups ran on 4 shards; {spread}")
    mm = _bit_mismatch(leaves["2x2"], leaves["off"])
    if mm is None:
        log("(4) FAIL 2x2 vs off: artifact structure differs")
        return False
    diff, total, n_leaves, per_layer, first_leaves = mm
    good = diff <= SHARDED_MISMATCH_TOL * total
    verdict = "bitwise-identical" if diff == 0 else "not bitwise"
    log(f"(4) {'ok  ' if good else 'FAIL'} 2x2 vs off: {verdict}; "
        f"{diff}/{total} elements in {n_leaves}/{len(leaves['2x2'])} "
        f"leaves differ (bound {SHARDED_MISMATCH_TOL:g}); per layer "
        f"{per_layer}; first differing layer by leaf {first_leaves}")
    return ok and good


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    cache = setup_compile_cache()
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def on_event(event, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and \
                name in cache_events:
            cache_events[name] += 1

    jax.monitoring.register_event_listener(on_event)
    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    log(f"(a) platform={platform} device_kind={kind} count={count} "
        f"compile_cache={cache}")
    if platform != "tpu":
        print(f"chip_smoke: JAX found platform {platform!r} "
              f"({kind}), not a TPU", file=sys.stderr)
        return 1
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {count}", file=sys.stderr)
        return 1

    from repro.configs.registry import get_config
    mc = get_config(ARCH).model
    os.makedirs(OUT, exist_ok=True)
    if args.chips == 4:
        phases = [("sharded quantize parity", phase_sharded)]
    else:
        widths = dict(tokens=16 * 512, d_model=mc.d_model, d_ff=mc.d_ff,
                      lanes=8, prefill=128, kv_heads=mc.num_kv_heads,
                      head_dim=mc.head_dim, cap=128 + 32 + 1, rpiq_rows=512)
        artifact = os.path.join(OUT, "opt-proxy.params.pkl")
        phases = [("kernel parity", lambda: phase_kernels(**widths)),
                  ("quantize", lambda: os.path.exists(quantize(OUT)[0])),
                  ("serve", lambda: phase_serve(artifact))]
    failed = []
    t_start = time.perf_counter()
    for name, fn in phases:
        try:
            ok = fn()
        except Exception:                    # noqa: BLE001 — reported
            traceback.print_exc()
            ok = False
        log(f"phase {name}: {'passed' if ok else 'FAILED'}")
        if not ok:
            failed.append(name)
            if name == "quantize":
                break                        # serve needs the artifact
    log(f"total {time.perf_counter() - t_start:.3f}s; persistent compile "
        f"cache {cache}: {cache_events['cache_hits']} hits, "
        f"{cache_events['cache_misses']} misses")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
