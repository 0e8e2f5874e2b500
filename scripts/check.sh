#!/usr/bin/env bash
# Tier-1 verify recipe (ROADMAP.md), executable: install dev deps if
# possible, then run the test suite. Extra args pass through to pytest.
set -euo pipefail
cd "$(dirname "$0")/.."

if ! python -c "import hypothesis" >/dev/null 2>&1; then
  pip install -r requirements-dev.txt \
    || echo "WARN: could not install dev deps (offline?); property tests" \
            "run on the deterministic fallback shim" >&2
fi

# docs cross-reference check: every *.md cited from src/ must exist
# (the DESIGN.md §2 citation dangled for three PRs — never again)
python scripts/docs_xref.py

# main leg runs everything except the heavy serving matrices, which get
# their own leg below (registered `serving` marker, pyproject.toml) — the
# bare tier-1 recipe (ROADMAP.md: pytest -x -q with no marker filter)
# still runs both sets in one pass
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
  python -m pytest -x -q -m "not serving" "$@"

# serving leg: continuous-scheduler + quantized-decode matrices
# (tests/test_serving.py, tests/test_kv_cache.py)
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
  python -m pytest -x -q -m serving

# multi-host-device leg: sharded group execution parity on a forced
# 4-device host mesh (tests/test_plan_sharded.py skips in the
# single-device run above — the main process keeps the one real device)
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
  python -m pytest -x -q tests/test_plan_sharded.py

# kernel leg: the fused-kernel parity pins under the registered `pallas`
# marker (stage-1 gptq_block + stage-2 rpiq_block interpret-mode suites)
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
  python -m pytest -x -q -m pallas tests/test_rpiq_kernel.py \
  tests/test_gptq_kernel.py

# robustness leg: the fault-injection suite (guardrail ladder, serving
# hardening, supervisor crash recovery, kill-and-resume parity — registered
# `faults` marker), plus one kill-and-resume smoke over real process
# boundaries: launch.quantize is interrupted by an armed fault, resumed
# from its step checkpoints (fp16 and int8 KV-cache configs), and the
# packed artifacts compared bitwise against a clean run
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
  python -m pytest -x -q -m faults
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python scripts/resume_smoke.py

# chaos leg: seeded randomized fault schedules across every registered
# site, driven through a supervised serving trace and a kill/resume
# quantize run at smoke scale; the invariant checker (exactly-one
# terminal status per request, token-identical recovery, self-consistent
# counters, bitwise-identical resumed artifacts) fails the leg on any
# violation. Three fixed seeds → the same schedules every CI run.
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
  python scripts/chaos_soak.py --seeds 0,1,2 --smoke

# stage-2 convergence smoke: Γ trajectories + early stop on both
# curvature modes (table5)
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run table5 --tiny

# overlap-pipeline smoke: the streaming layer-walk scheduler
# (quant.pipeline=overlap, core/stream.py) must stay runnable end to end
# through the quantize entry point (parity itself is pinned in
# tests/test_pipeline_stream.py)
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
  python -m repro.launch.quantize --arch opt-proxy --smoke quant.pipeline=overlap

# serving smoke: static-vs-continuous A/B bench path end to end
# (scheduler parity is pinned in tests/test_serving.py; --tiny does NOT
# rewrite the repo-root BENCH_serving.json), plus the launcher on the
# quantized continuous decode hot path (RTN-packed int4 weights through
# the slotted-cache scheduler — the deployment entry point)
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run serving --tiny
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
  python -m repro.launch.serve --arch opt-proxy --smoke --pack-rtn \
  --batch 2 --prompt-len 8 serve.max_new_tokens=4 serve.scheduler=continuous

# coverage leg: per-module line coverage for the serving + kernel surfaces
# (pytest-cov is in requirements-dev.txt; skipped with a note when the
# container has no network to install it — never a hard dependency)
if python -c "import pytest_cov" >/dev/null 2>&1; then
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m pytest -q \
    tests/test_serving.py tests/test_kv_cache.py tests/test_kv_codec.py \
    --cov=repro.serving --cov=repro.kernels --cov-report=term-missing
else
  echo "NOTE: pytest_cov not installed; skipping the coverage leg" >&2
fi
