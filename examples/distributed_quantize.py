"""Distributed (row-parallel) GPTQ+RPIQ: the TPU-native parallelization.

    PYTHONPATH=src python examples/distributed_quantize.py

GPTQ's column loop is sequential, but rows (output channels) are
independent given the shared Cholesky factor — so the quantizer shards
rows across the mesh and runs with ZERO collectives in the hot loop
(DESIGN.md §2, validated exactly in tests/test_distributed.py). This
example forces 8 host devices and shows the sharded call producing
bit-identical results to the single-device path.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core import hessian as hess
from repro.core.gptq import gptq_quantize
from repro.core.rpiq import rpiq_refine

Cout, Cin, N = 512, 256, 1024
W = jax.random.normal(jax.random.PRNGKey(0), (Cout, Cin)) * 0.1
X = jax.random.normal(jax.random.PRNGKey(1), (N, Cin))
st = hess.accumulate(hess.init_hessian(Cin), X)
Hd = hess.damped(st, 0.01)
U = hess.cholesky_inverse_upper(Hd)

res1 = gptq_quantize(W, U, bits=4, group_size=128, blocksize=128)

mesh = jax.make_mesh((8,), ("rows",), axis_types=(AxisType.Auto,))
shard = NamedSharding(mesh, P("rows", None))
rep = NamedSharding(mesh, P(None, None))
W_sh = jax.device_put(W, shard)
with mesh:
    res_sh = jax.jit(lambda w, u: gptq_quantize(
        w, u, bits=4, group_size=128, blocksize=128))(
        W_sh, jax.device_put(U, rep))
    np.testing.assert_allclose(np.asarray(res1.w_q),
                               np.asarray(jax.device_get(res_sh.w_q)),
                               rtol=1e-6, atol=1e-7)
    print("row-sharded GPTQ == single device (exact)")

    res2 = jax.jit(lambda w0, wfp, x, h, s, z: rpiq_refine(
        w0, wfp, x, h, s, z, h_count=jnp.asarray(N), alpha=0.3, t_max=5,
        exact_gram=True, block_size=128))(
        res_sh.w_q, W_sh, jax.device_put(X[-128:], rep),
        jax.device_put(Hd, rep), res_sh.scales, res_sh.zeros)
    print(f"row-sharded RPIQ: Γ {float(res2.loss_history[0]):.2f} → "
          f"{float(res2.proj_loss):.2f} on {len(jax.devices())} devices")
