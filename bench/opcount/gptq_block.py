"""Operations and HBM bytes of the GPTQ column sweep of one linear.

Lazy-batch GPTQ on W (out, in) with U (in, in), the upper factor of the
damped inverse Hessian, in blocks of ``bs`` columns. Operations, as the
algorithm needs them: the in-block error updates (out in bs/2
multiply-adds), the updates of the columns right of each block (out in^2/2
less the in-block part, as multiply-adds) and about 8 per weight to
quantise; the total is out in (in + 8) flops. Bytes, the least a sweep
moves: W and U read once, the quantised W and the per-group f32 scales
and zeros written once.
"""


def count(out: int, inp: int, group: int, bs: int = 128):
    del bs                                         # cancels in the total
    flops = float(out) * inp * (inp + 8)
    byts = 4.0 * (2 * out * inp + inp * inp + 2 * out * (inp // group))
    return flops, byts
