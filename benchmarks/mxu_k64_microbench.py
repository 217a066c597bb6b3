"""Test the round-4 'half-MXU K=64 contraction' hypothesis directly.

An earlier round's notes named head_dim-64 contractions (K=64) as the FSDP
attention bottleneck and asked for a head-packed K=128 variant.
Mathematically, packing two heads' features into one K=128 score contraction
computes the SUM of their score matrices — the only shape-true packing is
block-diagonal K/V, which doubles the MACs.  So packing can only win if the
MXU really runs K=64 at <= half the K=128 MAC rate.  This measures exactly
that, on the attention score geometry:

  a) per-head batched scores:  [BH, S, 64]  x [BH, 64, T]   (the real op)
  b) same-MAC K=128 control:   [BH, S, 128] x [BH, 128, T]  (2x MACs of (a))
  c) block-diag packed pairs:  [BH/2, S, 128] x [BH/2, 128, 2T]
     (= (b)'s MACs arranged as the packed-head score computation)

If (a) ~= (b) in wall time, K=64 runs at half rate and packing (c) could pay;
if (a) ~= (b)/2, XLA/MXU already handle K=64 efficiently and the hypothesis
is dead.  Run on the real chip: PYTHONPATH=/root/repo:$PYTHONPATH python
benchmarks/mxu_k64_microbench.py
"""

import time

import jax
import jax.numpy as jnp

B, H, S, T = 4, 32, 2048, 2048
N_ITER = 8


def bench(fn, *args):
    jitted = jax.jit(fn)  # hoisted: the timed loop must hit the fast path
    out = jitted(*args)
    float(jnp.asarray(out).ravel()[0].astype(jnp.float32))  # compile + barrier
    t0 = time.perf_counter()
    for _ in range(N_ITER):
        out = jitted(*args)
    float(jnp.asarray(out).ravel()[0].astype(jnp.float32))
    return (time.perf_counter() - t0) / N_ITER


def main():
    key = jax.random.PRNGKey(0)
    q64 = jax.random.normal(key, (B * H, S, 64), jnp.bfloat16)
    k64 = jax.random.normal(key, (B * H, 64, T), jnp.bfloat16)
    q128 = jax.random.normal(key, (B * H, S, 128), jnp.bfloat16)
    k128 = jax.random.normal(key, (B * H, 128, T), jnp.bfloat16)
    qp = jax.random.normal(key, (B * H // 2, S, 128), jnp.bfloat16)
    kp = jax.random.normal(key, (B * H // 2, 128, 2 * T), jnp.bfloat16)

    def mm(a, b):
        return jax.lax.batch_matmul(a, b, precision=jax.lax.Precision.DEFAULT)

    t_a = bench(mm, q64, k64)
    t_b = bench(mm, q128, k128)
    t_c = bench(mm, qp, kp)

    macs_a = B * H * S * T * 64
    macs_bc = 2 * macs_a
    print(f"device: {jax.devices()[0].device_kind}")
    print(f"(a) K=64  per-head scores : {1e3 * t_a:7.2f} ms  "
          f"({macs_a / t_a / 1e12:6.1f} TMAC/s)")
    print(f"(b) K=128 same shape ctrl : {1e3 * t_b:7.2f} ms  "
          f"({macs_bc / t_b / 1e12:6.1f} TMAC/s)")
    print(f"(c) K=128 block-diag pack : {1e3 * t_c:7.2f} ms  "
          f"({macs_bc / t_c / 1e12:6.1f} TMAC/s)")
    ratio = t_b / t_a
    print(f"K=128/K=64 wall ratio at 2x MACs: {ratio:.2f} "
          f"({'K=64 runs at ~half MXU rate — packing could pay' if ratio < 1.3 else 'K=64 is near full rate — packing cannot pay'})")
    print(f"packed (c) vs per-head (a): {t_c / t_a:.2f}x wall "
          f"({'WIN' if t_c < t_a else 'LOSS'} for packing)")


if __name__ == "__main__":
    main()
