"""Configuration system for the repro framework.

Plain dataclasses + dict overrides + a tiny CLI layer. No external deps.

Every launchable entry point takes ``--arch <id>`` (resolved through
``repro.configs.registry``) plus ``key=value`` dotted overrides, e.g.::

    python -m repro.launch.quantize --arch opt-proxy --smoke \
        quant.bits=4 quant.pipeline=overlap
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field, fields, is_dataclass
from typing import Any, Dict, List, Tuple


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

@dataclass
class MoEConfig:
    num_experts: int = 0            # 0 => dense
    top_k: int = 0
    d_ff_expert: int = 0            # per-expert hidden dim
    num_shared_experts: int = 0     # deepseek-style always-on experts
    first_dense_layers: int = 0     # leading layers that stay dense
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.001
    router_dtype: str = "float32"


@dataclass
class MLAConfig:
    """Multi-head latent attention (deepseek-v3)."""
    enabled: bool = False
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass
class SSMConfig:
    """Mamba-1 block configuration."""
    enabled: bool = False
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                # 0 => ceil(d_model/16)


@dataclass
class RGLRUConfig:
    """RG-LRU recurrent block (recurrentgemma)."""
    enabled: bool = False
    lru_width: int = 0              # 0 => d_model
    conv1d_width: int = 4


@dataclass
class ModelConfig:
    name: str = "tiny"
    family: str = "dense"           # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 0               # 0 => d_model // num_heads
    d_ff: int = 512
    vocab_size: int = 512
    max_seq_len: int = 8192

    # block pattern: list of block kinds, cycled over the layer stack.
    # kinds: "attn", "swa", "local", "rglru", "mamba", ("mla" via mla.enabled)
    block_pattern: Tuple[str, ...] = ("attn",)
    window_size: int = 0            # sliding/local attention window (0 = full)

    norm: str = "rmsnorm"           # rmsnorm | layernorm
    act: str = "silu"               # silu (gated) | gelu (ungated)
    gated_mlp: bool = True
    rope_theta: float = 10000.0
    use_rope: bool = True
    tie_embeddings: bool = False
    logits_softcap: float = 0.0
    attn_logits_softcap: float = 0.0
    dtype: str = "bfloat16"         # compute dtype
    param_dtype: str = "float32"    # master param dtype (training)

    # architecture add-ons
    moe: MoEConfig = field(default_factory=MoEConfig)
    mla: MLAConfig = field(default_factory=MLAConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    rglru: RGLRUConfig = field(default_factory=RGLRUConfig)

    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    encoder_seq_len: int = 1500     # frames after the (stubbed) conv frontend

    # modality frontend stub: "none" | "audio" | "vision"
    frontend: str = "none"
    frontend_tokens: int = 0        # patch/frame tokens prepended at prefill

    # multi-token prediction (deepseek)
    mtp_depth: int = 0
    mtp_loss_weight: float = 0.3

    def __post_init__(self):
        if self.head_dim == 0:
            self.head_dim = self.d_model // self.num_heads
        if self.ssm.enabled and self.ssm.dt_rank == 0:
            self.ssm.dt_rank = max(1, -(-self.d_model // 16))
        if self.rglru.enabled and self.rglru.lru_width == 0:
            self.rglru.lru_width = self.d_model

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.num_layers))


# ---------------------------------------------------------------------------
# Parallelism / runtime configuration
# ---------------------------------------------------------------------------

@dataclass
class ParallelConfig:
    data: int = 1
    model: int = 1
    pod: int = 1
    # strategy toggles
    fsdp: bool = True               # shard params/opt-state over data axis
    sp: bool = False                # Megatron-style sequence sharding over model
    ep: bool = True                 # expert parallel MoE over model axis
    pipeline_stages: int = 1        # >1 => GPipe over pod axis
    pp_microbatches: int = 8
    remat: str = "full"             # none | full | dots
    scan_layers: bool = True
    # distributed-optimization tricks
    grad_compression: str = "none"  # none | bf16 | int8 (explicit-DP mode)
    int8_optimizer_state: bool = False
    overlap_collectives: bool = True   # XLA latency hiding scheduler hints


@dataclass
class QuantConfig:
    bits: int = 4
    group_size: int = 128
    symmetric: bool = False
    percdamp: float = 0.01
    blocksize: int = 128            # GPTQ lazy-update block
    # RPIQ stage 2
    rpiq_iters: int = 5
    rpiq_alpha: float = 0.01        # paper-faithful step size
    rpiq_early_stop: bool = True
    rpiq_use_global_hessian: bool = True   # eq. 12-14: block-diag of damped H
    keep_best_projection: bool = True
    calib_batches: int = 8
    calib_batch_size: int = 16
    calib_seq_len: int = 512
    act_order: bool = False
    gptq_impl: str = "auto"         # auto | pallas | xla: stage-1 sweep
    #                                 backend (kernels/ops.py gptq_block —
    #                                 fused Pallas lazy-block kernel vs the
    #                                 vmapped fori_loop XLA body; "auto" =
    #                                 pallas on TPU when the (U + row tile)
    #                                 VMEM residency fits, else xla)
    rpiq_impl: str = "auto"         # auto | pallas | xla: stage-2 closed-
    #                                 loop backend (kernels/ops.py
    #                                 rpiq_block — fused Gauss–Seidel
    #                                 Pallas kernel, all rounds in one
    #                                 pallas_call, vs the vmapped
    #                                 while_loop XLA body; "auto" = pallas
    #                                 on TPU when the row tile + instance
    #                                 slab + block inverses fit VMEM, else
    #                                 xla)
    mesh: str = "off"               # sharded group execution (DESIGN.md
    #                                 §2.6): "off" = single device (default),
    #                                 "auto" = all devices on the data axis,
    #                                 "DxM" (e.g. "2x2") = explicit
    #                                 (data, model) mesh — group lanes shard
    #                                 over data, Cout row tiles over model;
    #                                 "DxMxE" (e.g. "1x1x8") adds an expert
    #                                 axis: groups made of stacked expert
    #                                 slabs shard lanes over expert (×data);
    #                                 non-divisible groups stay unsharded
    #                                 (launch/mesh.make_quant_mesh)
    resume: str = "off"             # off | auto: with "auto" and a ckpt_dir,
    #                                 quantize_model restarts a killed walk
    #                                 from the last completed LayerStep
    #                                 checkpoint — final artifacts are
    #                                 bitwise-identical to an uninterrupted
    #                                 run (tests/test_faults.py)
    ckpt_dir: str = ""              # "" disables layer checkpointing; set to
    #                                 a directory to persist completed
    #                                 LayerStep artifacts + stream state via
    #                                 distributed/checkpoint.py at every step
    #                                 boundary (fences always flush)
    ckpt_keep: int = 2              # retained step checkpoints in ckpt_dir
    guardrail: bool = True          # numerical guardrail ladder around the
    #                                 stage-1 Cholesky (core/plan.py): lanes
    #                                 with non-finite outputs (non-PSD /
    #                                 NaN Hessian) get escalating damping
    #                                 retries, then a per-group RTN fallback;
    #                                 outcomes counted in
    #                                 QuantReport.guardrail_stats
    guardrail_retries: int = 2      # damping-escalation rungs before RTN
    guardrail_damp_factor: float = 10.0  # percdamp multiplier per rung
    pipeline: str = "serial"        # layer-walk scheduling (core/stream.py,
    #                                 DESIGN.md §2.7): "serial" = capture →
    #                                 execute → propagate strictly alternate
    #                                 per layer (per-stage block_until_ready
    #                                 timing); "overlap" = streaming scheduler
    #                                 — executor dispatches stay async, the
    #                                 next layer's capture forward is
    #                                 dispatched speculatively on the
    #                                 pre-quantization residual stream while
    #                                 the executor is in flight, then repaired
    #                                 exactly after the scatter lands; routed
    #                                 MoE repairs at the plan level — only
    #                                 flipped routing assignments re-sort
    #                                 (core/pipeline._moe_members).
    #                                 Artifacts are bitwise-identical either
    #                                 way (tests/test_pipeline_stream.py,
    #                                 tests/test_moe_flip.py)
    moe_flip_budget: float = 0.5    # overlap + routed MoE: max fraction of
    #                                 (token, k) routing assignments allowed
    #                                 to flip between the speculative and
    #                                 post-quantization streams before the
    #                                 flip repair gives up on the speculative
    #                                 plans and re-plans the whole layer
    #                                 serially (counted as
    #                                 pipeline_stats["fallback_flip_budget"]);
    #                                 artifacts are bitwise-identical on
    #                                 either side of the budget


@dataclass
class TrainConfig:
    global_batch_size: int = 8
    seq_len: int = 128
    steps: int = 100
    lr: float = 3e-4
    warmup_steps: int = 10
    schedule: str = "cosine"        # cosine | wsd | constant
    wsd_stable_frac: float = 0.8
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    grad_accum: int = 1
    seed: int = 0
    log_every: int = 10
    ckpt_every: int = 200
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_keep: int = 3
    ckpt_async: bool = True


@dataclass
class ServeConfig:
    max_batch: int = 8              # decode lanes (continuous) / batch (static)
    max_new_tokens: int = 32
    temperature: float = 0.0        # 0 = greedy
    quantized: bool = True          # serve int4-packed weights
    prefill_chunk: int = 0          # 0 = single-shot prefill; >0 = prefill in
    #                                 chunks of this many positions (bounds
    #                                 per-tick prefill work so decode steps
    #                                 interleave — docs/SERVING.md). Chunked
    #                                 and single-shot prefill are
    #                                 logits/cache-equivalent (pinned in
    #                                 tests/test_serving.py)
    scheduler: str = "static"       # batching engine (docs/SERVING.md):
    #                                 "static" = engine.generate (whole batch
    #                                 padded to the slowest lane); "continuous"
    #                                 = serving/scheduler.ContinuousEngine —
    #                                 slot-based admit/evict mid-flight,
    #                                 chunked prefill interleaved with decode
    w4a16_impl: str = "auto"        # auto | pallas | xla: quantized-decode
    #                                 matmul backend for every QuantizedTensor
    #                                 dense on the serve path (kernels/ops.
    #                                 w4a16_matmul — same pattern and parity
    #                                 discipline as gptq_impl/rpiq_impl;
    #                                 "auto" = pallas on TPU, XLA ref
    #                                 elsewhere). Installed as the ops-level
    #                                 default around every engine trace; on a
    #                                 kernel fault the continuous engine
    #                                 degrades pallas→xla at runtime
    #                                 (docs/SERVING.md §Failure handling)
    kv_cache: str = "fp16"          # fp16 | int8: decode KV-cache precision.
    #                                 "int8" stores per-block absmax codes +
    #                                 f32 scales (kernels/kv_codec.py, the
    #                                 wire codec lifted into the cache) with
    #                                 per-lane error feedback on decode
    #                                 appends; MLA latents, recurrent states
    #                                 and enc-dec cross-KV stay bf16
    #                                 (docs/SERVING.md)
    kv_impl: str = "auto"           # auto | pallas | xla: int8-KV decode
    #                                 attention backend (kernels/ops.
    #                                 int8_kv_attention — fused dequant
    #                                 flash-decode kernel on TPU, XLA
    #                                 full-dequant oracle elsewhere; same
    #                                 dispatch/degradation discipline as
    #                                 w4a16_impl). No effect unless
    #                                 kv_cache="int8"
    request_timeout_s: float = 0.0  # per-request deadline (0 = none): a
    #                                 request past its deadline — queued,
    #                                 prefilling, parked, or decoding — is
    #                                 evicted with status "timeout" and its
    #                                 lane refilled the same tick
    max_queue: int = 0              # bounded admission queue (0 = unbounded):
    #                                 submits beyond this depth raise
    #                                 QueueFullError (counted backpressure
    #                                 instead of unbounded growth)
    decode_nan_guard: bool = True   # quarantine lanes whose decode logits go
    #                                 non-finite (evict only the poisoned
    #                                 lane, keep the batch decoding)
    supervise: bool = False         # wrap the continuous engine in
    #                                 serving/supervisor.SupervisedEngine:
    #                                 an engine crash (exception escaping
    #                                 step(), serve.engine_step fault, or a
    #                                 watchdog trip) rebuilds the engine and
    #                                 recovers in-flight requests by
    #                                 deterministic replay (docs/SERVING.md
    #                                 §Crash recovery)
    step_timeout_s: float = 0.0     # supervisor watchdog (0 = off): a tick
    #                                 whose clock() span exceeds this is
    #                                 treated as hung — the engine is
    #                                 rebuilt and its requests replayed
    #                                 (same injectable clock as deadlines)
    max_restarts: int = 3           # engine rebuilds the supervisor may
    #                                 perform before a crash loop surfaces
    #                                 as supervisor.EngineRestartExhausted
    #                                 (an explicit terminal error, never a
    #                                 silent retry forever)


@dataclass
class FaultsConfig:
    """Deterministic fault-injection plane (core/faults.py)."""
    arm: str = ""                   # comma-separated "site@trigger[:mode]"
    #                                 specs, e.g. "plan.stage1_executor@3" or
    #                                 "hessian.cholesky@1:nonpsd" — grammar
    #                                 and site table in core/faults.py
    seed: int = 0                   # seed for probabilistic (@pX) schedules


@dataclass
class Config:
    model: ModelConfig = field(default_factory=ModelConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    quant: QuantConfig = field(default_factory=QuantConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    faults: FaultsConfig = field(default_factory=FaultsConfig)


# ---------------------------------------------------------------------------
# Override machinery
# ---------------------------------------------------------------------------

def _coerce(value: str, current: Any) -> Any:
    if isinstance(current, bool):
        return value.lower() in ("1", "true", "yes", "on")
    if isinstance(current, int):
        return int(value)
    if isinstance(current, float):
        return float(value)
    if isinstance(current, tuple):
        parts = [p for p in value.split(",") if p]
        return tuple(parts)
    return value


def apply_overrides(cfg: Any, overrides: Dict[str, str]) -> Any:
    """Apply dotted-path string overrides to a (nested) dataclass, in place."""
    for key, value in overrides.items():
        parts = key.split(".")
        obj = cfg
        for p in parts[:-1]:
            obj = getattr(obj, p)
        leaf = parts[-1]
        if not hasattr(obj, leaf):
            raise KeyError(f"unknown config key: {key}")
        setattr(obj, leaf, _coerce(value, getattr(obj, leaf))
                if isinstance(value, str) else value)
    return cfg


def parse_overrides(argv: List[str]) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for a in argv:
        if "=" not in a:
            raise ValueError(f"override must be key=value, got {a!r}")
        k, v = a.split("=", 1)
        out[k] = v
    return out


def to_dict(cfg: Any) -> Any:
    if is_dataclass(cfg):
        return {f.name: to_dict(getattr(cfg, f.name)) for f in fields(cfg)}
    if isinstance(cfg, (list, tuple)):
        return [to_dict(x) for x in cfg]
    return cfg


def config_fingerprint(cfg: Any) -> str:
    import hashlib
    return hashlib.sha256(json.dumps(to_dict(cfg), sort_keys=True,
                                     default=str).encode()).hexdigest()[:16]
