"""Streaming layer-walk scheduler: one walker, two pipelines.

The quantization pipeline used to hold two near-duplicate serial walkers
(decoder-only and encoder-decoder) that each hand-rolled the same loop:
capture a layer's Hessians, execute its quant plan, scatter, propagate,
next layer. This module inverts that control flow. An architecture is
described once as a :class:`LayerWalker` — a flat list of
:class:`LayerStep` items (plus :class:`StreamSwitch` fences where the
residual stream changes, e.g. encoder → decoder) — and
:func:`run_walker` drains it under one of two schedules
(``quant.pipeline``):

``serial``
    The classic alternation, bit-for-bit the pre-walker behaviour:
    each step captures, executes (per-stage ``block_until_ready`` so the
    report's stage seconds measure compute), scatters, propagates.

``overlap``
    A two-deep stage queue built on JAX async dispatch. For step *i*:

    1. capture runs on the post-scatter stream of *i−1* (under overlap
       this is the **exact Hessian repair** of the speculative pass
       below — same compiled entries, same accumulation order, so the
       Hessian state is bitwise the serial one);
    2. the plan executes with **no per-stage sync** — stage dispatches
       are enqueued and timing lands at the step's report boundary;
    3. while the executor is in flight, step *i+1*'s jitted capture
       forward is dispatched **speculatively on the pre-quantization
       stream** (the capture-forward outputs of step *i*, which exist
       before the executor finishes). The speculative pass warms the
       capture jit entry and keeps the device queue full; its numeric
       results are discarded by the repair in (1), which is what keeps
       ``overlap`` bitwise-equal to ``serial``;
    4. scatter + propagate are enqueued, then the step's deferred
       executor records materialize and the per-step wall clock is
       taken (the only synchronization point in overlap mode).

    For a routed-MoE next step the speculative pass additionally
    dispatches the per-batch routing plans on its stream; at the MoE
    step's own turn ``pipeline._moe_members`` recomputes only the
    routing *head* on the true stream, reuses the sort/capacity
    structure bitwise for batches whose expert assignments did not flip,
    re-sorts flipped batches (the plan-level **flip repair**), and
    discards the speculative plans wholesale when the flip fraction
    exceeds ``quant.moe_flip_budget``. Per-expert Hessians always
    accumulate true-stream values, so MoE overlap stays bitwise serial.

    Speculation is skipped — the scheduler degrades to serial re-capture
    for that step — when the next step's signature marks the repair
    unsound (``LayerStep.repair_sound=False``; a test seam now that MoE
    repairs at the plan level), when the next item is a
    :class:`StreamSwitch` fence, or when the steps read different stream
    slots.

Per-run counters land in ``report.pipeline_stats`` — the
``serial_fallbacks`` total is split into per-reason counters
(``fallback_fence`` / ``fallback_cross_slot`` /
``fallback_repair_unsound`` / ``fallback_flip_budget``) and the MoE
flip-repair keeps its own ledger (``moe_spec_layers``,
``moe_plan_reuses``, ``moe_flip_repairs``, ``moe_flipped_assignments`` /
``moe_assignments``, ``moe_dropped_tokens``) — and the per-step wall
clocks in ``report.layer_step_seconds``; parity between the two
schedules is pinned in ``tests/test_pipeline_stream.py`` and
``tests/test_moe_flip.py``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import Config, to_dict
from repro.core import plan as qplan
from repro.core import spans
from repro.core.plan import LinearRecord, QuantReport

PIPELINE_MODES = ("serial", "overlap")


@dataclasses.dataclass
class LayerStep:
    """One quantizable layer of the walk.

    ``apply_fn(params, h, batch_index) -> h_out`` runs the layer;
    ``params`` is the layer's param subtree (pre-quantization) — either
    the dict itself or a zero-arg thunk producing it, so walkers over
    scan-stacked param trees slice each layer **lazily** at its turn
    instead of pinning every pre-quant slice for the whole walk (the
    scheduler also releases it once the step is stored). ``store`` puts
    the quantized subtree back into the caller's assembly. ``hs_slot``
    names the residual stream the step consumes and produces;
    ``fwd_key``/``batch_dependent`` key the jitted capture forward
    exactly as :func:`repro.core.pipeline._layer_forward_jit` expects.
    ``repair_sound=False`` marks the capture-ahead Hessian repair
    unsound for this step (routed MoE) — the overlap scheduler then
    degrades to serial re-capture for it; ``None`` (default) resolves
    lazily through ``pipeline._layer_repair_sound`` on the materialized
    params.
    """
    name: str
    params: Union[Dict, Callable[[], Dict]]
    apply_fn: Callable
    hs_slot: str
    fwd_key: Tuple
    store: Callable[[Dict], None]
    batch_dependent: bool = False
    repair_sound: Optional[bool] = None

    def resolve_params(self) -> Dict:
        if callable(self.params):
            with spans.span(spans.RESOLVE):
                self.params = self.params()
        return self.params

    def release_params(self) -> None:
        self.params = None


@dataclasses.dataclass
class StreamSwitch:
    """A fence between stream slots (e.g. encoder → decoder).

    ``run(streams)`` mutates the walker's stream dict — typically
    finalizing one slot (encoder final norm → cross-attention memory)
    and initializing the next. Speculation never crosses a switch, so
    the downstream slot always initializes from fully-propagated
    (post-quantization) upstream state, exactly as the serial walk does.
    """
    name: str
    run: Callable[[Dict[str, List[jax.Array]]], None]


WalkItem = Union[LayerStep, StreamSwitch]


def _repair_sound(qpipe, step: LayerStep) -> bool:
    """Resolve (and cache) a step's repair soundness — looked up through
    the pipeline module so tests can monkeypatch the predicate."""
    if step.repair_sound is None:
        step.repair_sound = qpipe._layer_repair_sound(step.resolve_params())
    return step.repair_sound


@dataclasses.dataclass
class LayerWalker:
    """An architecture's layer walk: streams + steps + reassembly.

    ``streams`` maps slot name → per-calibration-batch residual arrays
    (only the slots live at walk start; switches may add more).
    ``items`` must be constructible up front (builders bake closures,
    they do not read stream values — stream-dependent work belongs in a
    :class:`StreamSwitch`), which is what lets the scheduler look one
    step ahead. ``finalize()`` reassembles the quantized param tree from
    what the steps ``store``d.
    """
    streams: Dict[str, List[jax.Array]]
    items: Sequence[WalkItem]
    finalize: Callable[[], Dict]


# ---------------------------------------------------------------------------
# Layer-checkpointed resume (quant.ckpt_dir / quant.resume)
#
# At every step boundary the walker persists (a) the residual streams —
# the Hessian "slot state" every later capture derives from — and (b) the
# stored quantized subtrees of all completed steps, through
# distributed/checkpoint.py (atomic tmp+rename, async writer; fences
# flush synchronously). A killed run restarted with ``quant.resume=auto``
# replays only the StreamSwitch closures (host-side bookkeeping like the
# enc→dec memory publication), re-stores the checkpointed subtrees, and
# continues the walk from the first incomplete step. Because every step's
# inputs are exactly the checkpointed stream state the original run
# produced, the resumed walk's artifacts are bitwise-identical to an
# uninterrupted run (pinned in tests/test_faults.py, serial AND overlap).
#
# Cost note: each save snapshots the full stored-subtree dict to host, so
# checkpoint bandwidth grows with completed-walk size. That is the price
# of a self-contained latest-step checkpoint (retention gc keeps only
# ``quant.ckpt_keep``); smoke/tier-1 fixtures are tiny, and real runs
# amortize it against layer-quantization time.
# ---------------------------------------------------------------------------

def _resume_fingerprint(cfg: Config) -> str:
    """Config identity a checkpoint must match to be resumable: everything
    that shapes the walk EXCEPT the fault plane and the resume/ckpt knobs
    themselves (a resume run disarms faults and may relocate the dir)."""
    d = to_dict(cfg)
    d.pop("faults", None)
    for k in ("resume", "ckpt_dir", "ckpt_keep"):
        d.get("quant", {}).pop(k, None)
    return hashlib.sha256(json.dumps(d, sort_keys=True,
                                     default=str).encode()).hexdigest()[:16]


def _walk_ckpt_tree(streams: Dict[str, List[jax.Array]],
                    stored: Dict[str, Dict]) -> Dict:
    """Checkpoint payload: streams keyed slot/index + stored subtrees
    keyed by step name (both reconstructible blind via load_arrays)."""
    return {"streams": {slot: {f"{i:03d}": h for i, h in enumerate(hs)}
                        for slot, hs in streams.items()},
            "stored": stored}


def _restore_from_arrays(arrays: Dict[str, np.ndarray]
                         ) -> Tuple[Dict[str, List[jax.Array]],
                                    Dict[str, Dict]]:
    streams_ix: Dict[str, Dict[int, np.ndarray]] = {}
    stored: Dict[str, Any] = {}
    for path, arr in arrays.items():
        parts = path.split("/")
        if parts[0] == "streams":
            streams_ix.setdefault(parts[1], {})[int(parts[2])] = arr
        elif parts[0] == "stored":
            node = stored.setdefault(parts[1], {})
            for p in parts[2:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = arr
    streams = {slot: [jnp.asarray(ix[i]) for i in range(len(ix))]
               for slot, ix in streams_ix.items()}
    stored = jax.tree_util.tree_map(jnp.asarray, stored)
    return streams, stored


def _report_state(report: QuantReport, stats: Dict[str, Any]) -> Dict:
    return {"linears": [dataclasses.asdict(l) for l in report.linears],
            "seconds_stage1": report.seconds_stage1,
            "seconds_stage2": report.seconds_stage2,
            "layer_step_seconds": list(report.layer_step_seconds),
            "guardrail_stats": dict(report.guardrail_stats),
            "moe_capacity_dropped": dict(report.moe_capacity_dropped),
            "pipeline_counters": {k: v for k, v in stats.items()
                                  if isinstance(v, int)}}


def _restore_report(report: QuantReport, state: Dict,
                    stats: Dict[str, Any]) -> None:
    # records of older checkpoints carry a per-linear ``seconds`` timer
    # that the report no longer keeps
    report.linears[:] = [LinearRecord(**{**d, "shape": tuple(d["shape"])})
                         for d in ({k: v for k, v in d.items()
                                    if k != "seconds"}
                                   for d in state.get("linears", []))]
    report.seconds_stage1 = float(state.get("seconds_stage1", 0.0))
    report.seconds_stage2 = float(state.get("seconds_stage2", 0.0))
    report.layer_step_seconds[:] = state.get("layer_step_seconds", [])
    report.guardrail_stats.update(state.get("guardrail_stats", {}))
    for layer, n in state.get("moe_capacity_dropped", {}).items():
        report.moe_capacity_dropped[layer] = \
            report.moe_capacity_dropped.get(layer, 0) + int(n)
    for k, v in state.get("pipeline_counters", {}).items():
        if isinstance(stats.get(k), int):
            stats[k] += v


def run_walker(cfg: Config, walker: LayerWalker, report: QuantReport,
               fwd_cache: Dict, mesh=None,
               verbose: bool = False) -> Dict:
    """Drain the walker under ``cfg.quant.pipeline``; returns the
    finalized (quantized) param tree.

    Both schedules dispatch the same computations in the same order on
    the same inputs — ``overlap`` only moves synchronization points and
    adds discarded speculative work — so their artifacts (on-grid
    params, Γ histories, packed tensors) are bitwise-identical.
    """
    qc = cfg.quant
    mode = qc.pipeline
    if mode not in PIPELINE_MODES:
        raise ValueError(
            f"quant.pipeline must be one of {PIPELINE_MODES}, got {mode!r}")
    overlap = mode == "overlap"
    stats = {"mode": mode, "steps": 0, "spec_captures": 0, "repairs": 0,
             "serial_fallbacks": 0, "fallback_fence": 0,
             "fallback_cross_slot": 0, "fallback_repair_unsound": 0,
             "fallback_flip_budget": 0,
             "moe_spec_layers": 0, "moe_plan_reuses": 0,
             "moe_flip_repairs": 0, "moe_flipped_assignments": 0,
             "moe_assignments": 0, "moe_dropped_tokens": 0}
    items: List[WalkItem] = list(walker.items)

    ckpt = None
    fp = None
    start_idx = 0
    stored_snap: Dict[str, Dict] = {}   # completed-step subtrees (ckpt state)
    if qc.ckpt_dir:
        from repro.distributed.checkpoint import Checkpointer
        ckpt = Checkpointer(qc.ckpt_dir, keep=qc.ckpt_keep)
        fp = _resume_fingerprint(cfg)
        if qc.resume == "auto" and ckpt.latest_step() is not None:
            from repro.distributed.checkpoint import CheckpointIntegrityError
            try:
                arrays, extra = ckpt.load_arrays()
            except CheckpointIntegrityError as e:
                # damaged checkpoint (failed crc/manifest verification) is a
                # *different* condition from a config mismatch — warn with
                # the distinction and redo the walk from scratch; the next
                # step boundary overwrites the damaged state
                warnings.warn(
                    "quant.resume=auto: checkpoint in "
                    f"{qc.ckpt_dir!r} is corrupt ({e}) — starting fresh",
                    RuntimeWarning)
                arrays, extra = None, {}
            if arrays is None:
                pass
            elif extra.get("walk_fingerprint") != fp:
                warnings.warn(
                    "quant.resume=auto: checkpoint in "
                    f"{qc.ckpt_dir!r} was written by a different config "
                    "(fingerprint mismatch) — starting fresh", RuntimeWarning)
            else:
                start_idx = int(extra["item_idx"]) + 1
                streams_r, stored_snap = _restore_from_arrays(arrays)
                # Replay completed items host-side: switches rebuild their
                # closure side effects (e.g. the enc→dec fence publishing
                # the cross-attention memory), steps re-store their
                # checkpointed subtrees. Then overwrite the streams with
                # the checkpointed values — a replayed switch may reset
                # its output slot to walk-start state.
                walker.streams.clear()
                walker.streams.update({k: list(v)
                                       for k, v in streams_r.items()})
                for it in items[:start_idx]:
                    if isinstance(it, StreamSwitch):
                        it.run(walker.streams)
                    else:
                        it.store(stored_snap[it.name])
                        it.release_params()
                walker.streams.update({k: list(v)
                                       for k, v in streams_r.items()})
                _restore_report(report, extra.get("report", {}), stats)
                stats["resumed_at"] = start_idx
                if verbose:
                    print(f"  [resume] restarting at item "
                          f"{start_idx}/{len(items)}")

    def _save(idx: int) -> None:
        ckpt.save(idx, _walk_ckpt_tree(walker.streams, stored_snap),
                  extra={"item_idx": idx, "walk_fingerprint": fp,
                         "report": _report_state(report, stats)})

    try:
        _run_items(cfg, walker, report, fwd_cache, mesh, verbose, qc,
                   overlap, stats, items, start_idx, ckpt, _save,
                   stored_snap)
    finally:
        # join any in-flight async write before propagating — an orphaned
        # writer racing a subsequent resume's own saves could publish a
        # stale LATEST pointer
        if ckpt is not None:
            ckpt.wait()
    report.pipeline_stats = dict(stats)
    return walker.finalize()


def _run_items(cfg, walker, report, fwd_cache, mesh, verbose, qc, overlap,
               stats, items, start_idx, ckpt, save_fn,
               stored_snap):
    from repro.core import pipeline as qpipe   # circular-at-import only

    spec_for: Optional[LayerStep] = None
    spec_routes = None                # MoE routing plans from the spec pass
    for idx, item in enumerate(items):
        if idx < start_idx:
            continue                  # replayed from checkpoint above
        if isinstance(item, StreamSwitch):
            item.run(walker.streams)
            spec_for = None
            spec_routes = None
            if ckpt is not None:
                save_fn(idx)
                ckpt.wait()           # fences always flush
            continue
        # one layer step; its self time is what no child span covers
        with spans.span(spans.STEP, layer=idx):
            t_step = time.perf_counter()
            hs = walker.streams[item.hs_slot]
            # speculation eligibility is knowable up front (it only depends
            # on the NEXT item's signature/slot), so the pre-quant outputs
            # are retained exactly when the capture-ahead below will consume
            # them. The repair-soundness predicate resolves lazily and only
            # under overlap (short-circuit), materializing nxt's params at
            # most one step early — they are about to be needed anyway.
            nxt = items[idx + 1] if idx + 1 < len(items) else None
            spec_block: Optional[str] = None
            if overlap and nxt is not None:
                if isinstance(nxt, StreamSwitch):
                    spec_block = "fence"
                elif nxt.hs_slot != item.hs_slot:
                    spec_block = "cross_slot"
                elif not _repair_sound(qpipe, nxt):
                    spec_block = "repair_unsound"
            can_spec = overlap and nxt is not None and spec_block is None
            # 1. capture — under overlap this re-propagates the taps on the
            # repaired (post-scatter) stream: the exact Hessian repair of
            # the speculative pass, riding its compiled entries.
            cap = qpipe.capture_layer(cfg, item, hs, fwd_cache,
                                      collect_h_out=can_spec)
            routes = spec_routes if spec_for is item else None
            if spec_for is item:
                stats["repairs"] += 1
            spec_for = None
            spec_routes = None
            # 2. plan — spec routing plans (if any) feed the MoE flip repair
            new_params, dense_names, plan = qpipe.plan_layer(
                cfg, item, cap, hs, report=report, stats=stats,
                spec_routes=routes)
            # 3. execute — async under overlap: per-stage sync and record
            # materialization defer to this step's report boundary below.
            deferred: Optional[List[Callable[[], None]]] = \
                [] if overlap else None
            results = qplan.execute_plan(qc, plan, report, mesh=mesh,
                                         sync=not overlap, deferred=deferred)
            # 4. scatter on-grid weights (+ grids) back into the subtree
            with spans.span(spans.SCATTER):
                qpipe.scatter_layer(new_params, dense_names, cap, results)
            # 5. capture-ahead: dispatch the NEXT step's capture forward on
            # THIS step's pre-quantization outputs while the executor is in
            # flight. Discarded at the repair in (1) — overlap stays exact.
            if can_spec:
                spec_cap = qpipe.capture_layer(cfg, nxt, cap.h_out,
                                               fwd_cache, speculative=True)
                spec_for = nxt
                spec_routes = spec_cap.spec_routes
                stats["spec_captures"] += 1
            elif spec_block is not None:
                stats["serial_fallbacks"] += 1
                stats["fallback_" + spec_block] += 1
            # 6. propagate quantized activations
            with spans.span(spans.PROPAGATE):
                walker.streams[item.hs_slot] = qpipe.propagate_layer(
                    item, new_params, hs, fwd_cache)
            item.store(new_params)
            # 7. report boundary: materialize the deferred executor records
            # and take the per-layer-step wall clock — the only sync in
            # overlap mode (speculative work stays in flight across it).
            item.release_params()    # drop the pre-quant slice progressively
            if deferred:
                with spans.span(spans.RESULTS):
                    for fin in deferred:
                        fin()
            if overlap:
                jax.block_until_ready(walker.streams[item.hs_slot][-1])
            report.layer_step_seconds.append(time.perf_counter() - t_step)
            stats["steps"] += 1
            if ckpt is not None:
                # step boundary: the step's artifacts + post-propagate
                # stream state become durable (async; save() host-snapshots
                # first, so in-flight speculative work keeps the device busy)
                stored_snap[item.name] = new_params
                save_fn(idx)
            if verbose:
                print(f"  {item.name}: {report.summary()}")
