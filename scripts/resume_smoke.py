#!/usr/bin/env python
"""Kill-and-resume smoke over real process boundaries (scripts/check.sh leg).

The in-process resume matrix lives in tests/test_faults.py; this script
pins the part a test process cannot: a *separate* ``launch.quantize``
process dies mid-run (armed ``plan.stage1_executor`` fault → nonzero exit),
a second invocation with ``quant.resume=auto`` picks up its step
checkpoints, and the final packed artifact is bitwise-identical to a clean
single-shot run.

A second kill-and-resume pass runs under ``serve.kv_cache=int8`` (the
serve config participates in the resume fingerprint, so the killed and
resumed runs must agree on it), and the resumed artifact is then served
through ``launch.serve`` on the int8-KV continuous decode path — the
resume plane and the quantized cache exercised *together*, over the same
process boundaries a real deployment restart crosses.

    PYTHONPATH=src python scripts/resume_smoke.py
"""
from __future__ import annotations

import os
import pickle
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))
ARCH = "opt-proxy"
COMMON = ["--arch", ARCH, "--smoke"]
CALIB = ["quant.calib_batches=2", "quant.calib_batch_size=4",
         "quant.calib_seq_len=32"]


def run_quantize(out_dir: str, extra, expect_rc: int) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "repro.launch.quantize",
           *COMMON, "--out", out_dir, *CALIB, *extra]
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True)
    if p.returncode != expect_rc and not (expect_rc != 0 and p.returncode):
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(
            f"expected rc={'nonzero' if expect_rc else 0}, "
            f"got {p.returncode}: {' '.join(cmd)}")


def run_serve(params: str, extra) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "repro.launch.serve",
           *COMMON, "--params", params, "--batch", "2",
           "--prompt-len", "8", "serve.max_new_tokens=6", *extra]
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True)
    if p.returncode != 0:
        print(p.stdout)
        print(p.stderr, file=sys.stderr)
        raise SystemExit(f"serve failed (rc={p.returncode}): {' '.join(cmd)}")


def load_leaves(path: str):
    """Artifact leaves as host arrays. This process only starts children
    that need the device, so it must never initialise a JAX backend itself
    (on an accelerator it would then hold the chip the next child needs):
    artifacts hold numpy leaves, and the check below keeps it that way."""
    import jax                      # registers QuantizedTensor pytree nodes
    import numpy as np
    import repro                    # noqa: F401
    import repro.kernels.ops        # noqa: F401
    from jax._src import xla_bridge
    with open(path, "rb") as f:
        tree = pickle.load(f)
    leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]
    if xla_bridge.backends_are_initialized():
        raise SystemExit("resume_smoke: loading an artifact initialised a "
                         "JAX backend in the parent process")
    return leaves


def main() -> None:
    work = tempfile.mkdtemp(prefix="resume_smoke_")
    try:
        ref_dir = os.path.join(work, "ref")
        res_dir = os.path.join(work, "res")
        ckpt = os.path.join(work, "ckpt")

        print("[resume_smoke] 1/5 clean reference run")
        run_quantize(ref_dir, [], expect_rc=0)

        print("[resume_smoke] 2/5 killed run (plan.stage1_executor@4)")
        run_quantize(res_dir, [
            f"quant.ckpt_dir={ckpt}", "quant.resume=auto",
            "faults.arm=plan.stage1_executor@4"], expect_rc=1)
        if not any(d.startswith("step_") for d in os.listdir(ckpt)):
            raise SystemExit("killed run left no step checkpoint behind")

        print("[resume_smoke] 3/5 resumed run")
        run_quantize(res_dir, [
            f"quant.ckpt_dir={ckpt}", "quant.resume=auto"], expect_rc=0)

        name = next(f for f in os.listdir(ref_dir)
                    if f.endswith(".params.pkl"))
        import numpy as np

        def check_bitwise(out_dir: str, what: str) -> None:
            ref = load_leaves(os.path.join(ref_dir, name))
            res = load_leaves(os.path.join(out_dir, name))
            if len(ref) != len(res):
                raise SystemExit(
                    f"{what}: leaf count mismatch: {len(ref)} vs {len(res)}")
            for i, (a, b) in enumerate(zip(ref, res)):
                if a.dtype != b.dtype or not np.array_equal(
                        a.view(np.uint8), b.view(np.uint8)):
                    raise SystemExit(f"{what}: leaf {i} differs after resume")
            print(f"[resume_smoke] {what}: {len(ref)} leaves "
                  "bitwise-identical after kill+resume")

        check_bitwise(res_dir, "fp16-kv matrix")

        # same matrix under serve.kv_cache=int8: the serve config is part
        # of the resume fingerprint, so kill and resume must agree on the
        # override — and the quantize output itself is serve-independent,
        # so the artifact must still match the fp16-kv reference bitwise
        int8_dir = os.path.join(work, "res_int8")
        ckpt8 = os.path.join(work, "ckpt_int8")
        KV8 = ["serve.kv_cache=int8"]
        print("[resume_smoke] 4/5 killed+resumed run under "
              "serve.kv_cache=int8")
        run_quantize(int8_dir, [
            f"quant.ckpt_dir={ckpt8}", "quant.resume=auto", *KV8,
            "faults.arm=plan.stage1_executor@4"], expect_rc=1)
        if not any(d.startswith("step_") for d in os.listdir(ckpt8)):
            raise SystemExit("int8 killed run left no step checkpoint behind")
        run_quantize(int8_dir, [
            f"quant.ckpt_dir={ckpt8}", "quant.resume=auto", *KV8],
            expect_rc=0)
        check_bitwise(int8_dir, "int8-kv matrix")

        print("[resume_smoke] 5/5 serve resumed artifact on int8-KV "
              "continuous path")
        run_serve(os.path.join(int8_dir, name), [
            "serve.scheduler=continuous", *KV8])
        print("[resume_smoke] OK: kill+resume matrix holds for fp16 and "
              "int8 KV cache; resumed artifact serves")
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
