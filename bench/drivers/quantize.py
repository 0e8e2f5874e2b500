"""Quantize driver: whole RPIQ quantize jobs, back to back.

A job is what a user runs for an artifact: ``core.pipeline.quantize_model``
on fresh calibration tokens drawn from the seed, ``pack_for_serving``, and
the packed int4 artifact brought to the host. Set-up makes the float model
on the device from the seed and runs one whole job, which compiles every
program the window uses.

``quant_layers_per_s`` counts the layer steps completed in the window. A
step's completion time is its job's call time plus the walker's build
(``seconds_total`` less the steps) plus the running sum of
``report.layer_step_seconds`` (synchronised: ``quant.pipeline=serial``),
so the job still running when the window closes counts the steps it
finished inside it. Packing and the copy to the host count against the
window. A traced run profiles the window's first job, whole.

Correctness: one job of the window, drawn from the seed, is checked
against the plain reference (``bench/reference.py``), which quantizes the
same float model along its own calibration chain: GPTQ, then stage 2 on
the last batch. Two numbers are compared, each over every linear:

- ``quant_excess_error``: the worst linear's output error
  ``tr(dW H dW^T) / tr(W H W^T)`` of the artifact over that of the
  reference's weights, less 1 (H: the reference's calibration Hessian);
- ``stage2_residual_gap``: stage 2's own result, the share of the single
  instance's output residual that its closed loop removed,
  ``1 - residual_last / residual_first`` from the job's report, against
  the reference's share: the median linear's gap, each linear's over the
  size of the reference's share of that linear or of the median linear,
  whichever is larger (``stage2_gaps``; the worst linear's and the
  worst kind's are logged).

With ``hooks["control"]`` the reference's own chain, computed in
bfloat16, takes the program's place in the window (the control).
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from bench import gen
from bench import program as prog
from bench import reference as ref
from bench import weights as W


CHECKED_JOBS = 2        # the check draws one of the window's first jobs


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run(ctx) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.core.pipeline import pack_for_serving, quantize_model

    cfgd = ctx.config
    cfg = prog.program_config(cfgd)
    dims = W.Dims.of(cfgd["model"])
    qc = cfg.quant
    shape = (qc.calib_batches, qc.calib_batch_size, qc.calib_seq_len)
    params = prog.float_model(dims, ctx.seed)
    jax.block_until_ready(params)
    # the tests and bench/control.py put the control or a fault in the
    # program's place through these
    quantize = ctx.hooks.get("quantize_model", quantize_model)
    if ctx.hooks.get("control"):
        def quantize(cfg, params, calib):
            return _reference_quantize(dims, ctx.seed, cfgd["quant"],
                                       params, calib, "bf16")
    pack = ctx.hooks.get("pack_for_serving", pack_for_serving)

    def job(j: int):
        toks = gen.calibration_tokens(ctx.seed, j, *shape, dims.vocab_size)
        calib = [{"tokens": jnp.asarray(toks[b])} for b in range(shape[0])]
        t_call = time.perf_counter()
        with _annotate("quantize_model"):
            params_q, report = quantize(cfg, params, calib)
        with _annotate("pack_for_serving"):
            packed = jax.device_get(pack(cfg, params_q))
        return t_call, report, packed

    job(-1)                                  # warm-up: compiles it all
    ctx.log(f"warm-up job done; calibration {shape}")
    jobs: List[Dict] = []
    artifacts: Dict[int, Dict] = {}
    t0 = ctx.window_start()
    k = 0
    while time.perf_counter() - t0 < ctx.seconds:
        traced = ctx.trace_on and k == 0
        if traced:
            ctx.trace_start()
        t_call, report, packed = job(k)
        t_end = time.perf_counter()
        if traced:
            ctx.trace_stop()
            ctx.records["traced_job"] = k
        steps = np.asarray(report.layer_step_seconds)
        build = report.seconds_total - float(steps.sum())
        done = (t_call - t0) + build + np.cumsum(steps)
        jobs.append({"start": t_call - t0, "end": t_end - t0,
                     "report": report, "done": done})
        if k < CHECKED_JOBS:
            artifacts[k] = packed
        del packed
        k += 1
    ctx.window_end()
    ctx.snapshot_memory()
    n_steps = int(sum(np.sum(j["done"] <= ctx.seconds) for j in jobs))
    e2e = {"quant_layers_per_s": n_steps / ctx.seconds,
           "setup_s": ctx.setup_s}
    fb = jobs[-1]["report"].kernel_fallbacks
    ctx.log(f"jobs={len(jobs)} steps_in_window={n_steps} "
            f"job_s={[round(j['end'] - j['start'], 3) for j in jobs]} "
            f"fallbacks={fb}")
    ctx.records.update(jobs=jobs, dims=dims, calib_shape=shape)

    # -- correctness: one job of the window against the reference's chain
    del params
    gc.collect()
    pick = int(gen.rng_for(ctx.seed, 3).integers(0, len(artifacts)))
    toks = gen.calibration_tokens(ctx.seed, pick, *shape, dims.vocab_size)
    t_ref = time.perf_counter()
    checks = compare(ctx, dims, toks, artifacts[pick]["blocks"][0]["sub0"],
                     jobs[pick]["report"])
    ctx.log(f"reference: job {pick}, {time.perf_counter() - t_ref:.1f} s")
    return {"attempted": len(jobs), "failed": 0, "e2e": e2e,
            "checks": checks, "records": {}}


def compare(ctx, dims, tokens: np.ndarray, art: Dict, report) -> List:
    """The numbers compared, each with its limit (module docstring)."""
    quant = ctx.config["quant"]
    group = int(quant["group_size"])
    stage2 = _stage2_by_layer(report.linears)
    excess, drops, rounds = -np.inf, [], []
    moved = total = 0
    for layer, lins in ref.quant_chain(dims, ctx.seed, tokens, quant):
        for parent, leaf in ref.LINEARS:
            lin = lins[leaf]
            qt = art[parent][leaf]["w"]
            wq = ref.dequant_packed(qt.packed[layer], qt.scales[layer],
                                    qt.zeros[layer], group)
            e_ref = float(ref.proxy_error(lin.w, lin.wq, lin.hess))
            e_prog = float(ref.proxy_error(lin.w, wq, lin.hess))
            excess = max(excess, e_prog / e_ref - 1.0)
            hist = stage2.get((layer, leaf), [])
            drops.append((_drop(hist), _drop(np.asarray(lin.residual))))
            rounds.append((max(len(hist) - 1, 0), int(lin.rounds)))
            moved += int(np.sum(np.asarray(lin.wq) != np.asarray(lin.w1)))
            total += lin.wq.size
    d_prog, d_ref = np.asarray(drops).T
    gaps = stage2_gaps(d_prog, d_ref)
    kinds = gaps.reshape(-1, len(ref.LINEARS))          # (layers, kinds)
    r_prog, r_ref = (np.bincount(r, minlength=quant["rpiq_iters"] + 1)
                     .tolist() for r in np.asarray(rounds).T)
    by_kind = np.median(kinds, axis=0)
    worst = int(np.argmax(gaps))
    ctx.log(f"stage 2's worst linear: number {worst} (layer {worst // 6}), "
            f"share removed {d_prog[worst]:.6g} by the program, "
            f"{d_ref[worst]:.6g} by the reference; gap {gaps[worst]:.6g}")
    ctx.log(f"stage 2 removed {np.median(d_ref):.6g} of the instance "
            f"residual on the reference's median linear, "
            f"{np.median(d_prog):.6g} on the program's; median gap "
            f"{np.median(gaps):.6g}; by kind {np.round(by_kind, 6).tolist()};"
            f" linears by rounds run: program {r_prog}, reference {r_ref};"
            f" the reference's stage 2 moved {moved} of {total} weights off "
            f"its stage-1 result")
    lim = ctx.limits
    return [("quant_excess_error", excess, lim["quant_excess_error"]),
            ("stage2_residual_gap", float(np.median(gaps)),
             lim["stage2_residual_gap"])]


def stage2_gaps(d_prog: np.ndarray, d_ref: np.ndarray) -> np.ndarray:
    """Each linear's gap between the shares of the instance residual
    that the program's and the reference's rounds removed, over the
    reference's share of that linear or of the median linear, whichever
    is larger in size. A share is negative where a round raised the
    residual and early stop ended the loop, so sizes, not signs, scale
    the gap: it is never negative."""
    size = np.abs(d_ref)
    return np.abs(d_prog - d_ref) / np.maximum(size, np.median(size))


def _drop(history) -> float:
    """The share of the first residual that the rounds run removed: 0
    where no round ran."""
    h = [float(g) for g in history if np.isfinite(g)]
    return 1.0 - h[-1] / h[0] if len(h) > 1 and h[0] > 0 else 0.0


def _stage2_by_layer(linears) -> Dict:
    """{(layer, leaf): residual history} from a report's records, which
    come layer by layer, one record per linear."""
    out: Dict = {}
    layer = 0
    for rec in linears:
        leaf = rec.name.rsplit(".", 1)[-1]
        if (layer, leaf) in out:
            layer += 1
        out[(layer, leaf)] = rec.gamma
    return out


# ---------------------------------------------------------------------------
# The control: the reference's chain in the program's place
# ---------------------------------------------------------------------------

@dataclass
class _Record:
    name: str
    shape: tuple
    gamma: List[float]
    iters: int
    mode: str = "rpiq"


@dataclass
class _Report:
    """The fields of the program's report that the window reads."""
    layer_step_seconds: List[float] = field(default_factory=list)
    seconds_total: float = 0.0
    seconds_stage1: float = 0.0
    seconds_stage2: float = 0.0
    linears: List[_Record] = field(default_factory=list)
    kernel_fallbacks: Dict = field(default_factory=dict)


def _reference_quantize(dims, seed: int, quant: Dict, params: Dict,
                        calib: List[Dict], dtype_name: str):
    """``quantize_model``'s contract served by the reference's chain:
    the float model with each linear's weights replaced by the chain's,
    their stage-1 grids beside them (what ``pack_for_serving`` packs on),
    and a report."""
    import jax
    import jax.numpy as jnp
    t0 = time.perf_counter()
    tokens = np.stack([np.asarray(b["tokens"]) for b in calib])
    rep = _Report()
    layers: Dict = {leaf: [] for _, leaf in ref.LINEARS}
    t = time.perf_counter()
    for _, lins in ref.quant_chain(dims, seed, tokens, quant, dtype_name):
        jax.block_until_ready(lins)
        for parent, leaf in ref.LINEARS:
            lin = lins[leaf]
            layers[leaf].append(lin)
            rep.linears.append(_Record(f"{parent}.{leaf}", lin.wq.shape,
                                       [float(g) for g in
                                        np.asarray(lin.residual)],
                                       int(lin.rounds)))
        now = time.perf_counter()
        rep.layer_step_seconds.append(now - t)
        t = now
    sub = dict(params["blocks"][0]["sub0"])
    for parent, leaf in ref.LINEARS:
        lins = layers[leaf]
        node = dict(sub[parent][leaf])
        node["w"] = jnp.stack([lin.wq.T for lin in lins])
        node["qscales"] = jnp.stack([lin.scales for lin in lins])
        node["qzeros"] = jnp.stack([lin.zeros for lin in lins])
        sub[parent] = dict(sub[parent], **{leaf: node})
    rep.seconds_total = time.perf_counter() - t0
    return dict(params, blocks=[{"sub0": sub}]), rep
