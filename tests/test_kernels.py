"""Per-kernel interpret-mode validation against the pure-jnp oracles,
swept over shapes and dtypes (assignment requirement c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_rmsnorm import fused_residual_rmsnorm_pallas
from repro.kernels.ref import (flash_attention_ref,
                               fused_residual_rmsnorm_ref)


@pytest.mark.parametrize("t,d", [(8, 64), (64, 128), (128, 384), (56, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_rmsnorm_matches_oracle(t, d, dtype):
    key = jax.random.PRNGKey(t + d)
    x = jax.random.normal(key, (t, d), dtype)
    r = jax.random.normal(jax.random.PRNGKey(1), (t, d), dtype)
    w = (jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (d,))) + 0.5
         ).astype(dtype)
    o_ref, r_ref = fused_residual_rmsnorm_ref(x, r, w)
    o_k, r_k = fused_residual_rmsnorm_pallas(x, r, w, interpret=True,
                                             block_tokens=32)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_ref, np.float32), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(r_k, np.float32),
                               np.asarray(r_ref, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize(
    "b,sq,sk,kvh,g,dh,causal,window,off",
    [
        (2, 40, 72, 2, 3, 16, True, 0, 32),   # GQA + chunked offset
        (1, 64, 64, 1, 4, 32, True, 24, 0),   # sliding window
        (2, 33, 65, 2, 1, 16, False, 0, 0),   # bidirectional, ragged blocks
        (1, 16, 128, 4, 2, 64, True, 0, 112),  # decode-ish long kv
    ])
def test_flash_attention_matches_oracle(b, sq, sk, kvh, g, dh, causal,
                                        window, off):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, sq, kvh, g, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, kvh, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, kvh, dh))
    qpos = jnp.broadcast_to(jnp.arange(off, off + sq)[None], (b, sq))
    kpos = jnp.broadcast_to(jnp.arange(sk)[None], (b, sk))
    o = flash_attention(q, k, v, qpos, kpos, causal=causal, window=window,
                        block_q=16, block_kv=32, interpret=True)
    refs = [flash_attention_ref(q[i].reshape(sq, kvh * g, dh), k[i], v[i],
                                causal=causal, window=window, q_offset=off
                                ).reshape(sq, kvh, g, dh)
            for i in range(b)]
    np.testing.assert_allclose(o, jnp.stack(refs), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("n,t,d", [(4, 16, 32), (8, 32, 64), (2, 8, 128)])
def test_ring_ar_rmsnorm_multidevice(n, t, d, tmp_path):
    """The paper's fused AllReduce-RMSNorm kernel, validated on n simulated
    devices via the Pallas TPU interpret machinery (subprocess)."""
    from conftest import run_distributed
    code = f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.kernels.ring_ar_rmsnorm import ring_fused_ar_rmsnorm
from repro.kernels.ref import ring_ar_rmsnorm_ref
N, T, D = {n}, {t}, {d}
mesh = jax.make_mesh((N,), ('x',), axis_types=(jax.sharding.AxisType.Auto,))
xs = jax.random.normal(jax.random.PRNGKey(0), (N, T, D), jnp.float32)
res = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.float32)
w = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (D,))) + 0.5
def f(x_shard, res_shard):
    return ring_fused_ar_rmsnorm(x_shard[0], res_shard, w, axis_name='x',
                                 n_dev=N, interpret=True)
g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P('x'), P('x')),
                          out_specs=(P(None), P('x')), check_vma=False))
# fence before the reference: the interpreter's callbacks dispatch JAX
# ops of their own, and eager dispatch racing a still-running interpreted
# kernel deadlocks it on 8 devices
out, new_res = jax.block_until_ready(g(xs, res))
ref_outs, ref_res = ring_ar_rmsnorm_ref(
    [xs[i] for i in range(N)],
    [res.reshape(N, T // N, D)[i] for i in range(N)], w)
np.testing.assert_allclose(out, ref_outs[0], rtol=2e-5, atol=2e-5)
np.testing.assert_allclose(new_res, jnp.concatenate(ref_res, 0),
                           rtol=2e-5, atol=2e-5)
print('PASS')
"""
    run_distributed(code, n_devices=n)
