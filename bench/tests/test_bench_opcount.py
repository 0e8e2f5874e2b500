"""The op and byte functions against counts done by hand."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import flops
from bench.harness import load_module

OPC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "opcount")


def _oc(name):
    return load_module(os.path.join(OPC, name + ".py"), "oc_" + name)


@pytest.mark.parametrize("n,d", [(8192, 768), (1000, 100)])
def test_hessian_by_hand(n, d):
    f, byts = _oc("hessian_accum").count(n, d)
    assert f == 2 * n * d * d
    assert byts == n * d * 4 + d * d * 4      # x read once, H written once


@pytest.mark.parametrize("out,inp", [(768, 3072), (3072, 768)])
def test_gptq_by_hand(out, inp):
    f, byts = _oc("gptq_block").count(out, inp, 128)
    assert f == out * inp * (inp + 8)
    assert byts == 4 * (2 * out * inp + inp * inp + 2 * out * (inp // 128))


def test_quant_job_flops_by_hand():
    dims = NS(num_layers=2, d_model=8, d_ff=32, vocab_size=10)
    per_tok = 2 * (4 * 8 * 8 + 2 * 8 * 32)            # six linears a layer
    # one batch of 1 sequence of 4 positions: pairs 1+2+3+4 = 10
    fwd = 4 * per_tok + 4 * 8 * 10
    rpiq = NS(shape=(8, 32), mode="rpiq", iters=2)
    gptq = NS(shape=(32, 8), mode="gptq", iters=0)
    want = (2 * 2 * fwd                                # capture + propagate
            + 2 * 2.0 * 4 * (3 * 8 * 8 + 32 * 32)      # q/k/v, o, up, down
            + 2.0 * 32 ** 3 + 8 * 32 * 32              # factor + sweep
            + 4.0 * 4 * 32 * 8                         # stage 2's start
            + 2 * (4.0 * 4 * 32 * 8 + 2.0 * 128 * 32 * 8)
            + 2.0 * 8 ** 3 + 32 * 8 * 8)
    assert flops.quant_job(dims, (1, 1, 4), [rpiq, gptq], 4) == want
