"""Micro-bench of the Pallas kernels' XLA-reference paths (the numbers
that matter on CPU are the *oracle* paths; the kernels themselves are
interpret-mode here and compiled only on real TPU).  Reports us/call for
small shapes + the analytic VMEM footprint of each kernel's BlockSpec
tiling.

Timing discipline (PR 4): each program is AOT-compiled
(``jit().lower().compile()``) so compile time never leaks into a timed
window, warmed up, and every timed window ends in ``block_until_ready``;
compile time rides in the derived field (``compile_ms``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _bench(fn, *args, iters=5):
    """AOT-compile ``fn``; returns (us_per_call, compile_ms)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_ms = (time.perf_counter() - t0) * 1e3
    for _ in range(2):                      # warmup
        out = compiled(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6, compile_ms


def main():
    key = jax.random.PRNGKey(0)
    out = []

    # flash_attention oracle
    B, T, H, hd = 2, 512, 4, 64
    q, k, v = [jax.random.normal(kk, (B, T, H, hd))
               for kk in jax.random.split(key, 3)]
    us, cms = _bench(ref.flash_attention, q, k, v)
    vmem_kib = (128 * hd * 4 * 3 + 128 * 128 * 4) / 1024
    out.append(f"kernel_flash_ref_{T}t,{us:.0f},"
               f"vmem_per_block_kib={vmem_kib:.0f};compile_ms={cms:.0f}")

    # ssd oracle
    B, T, nh, P, N = 2, 512, 8, 64, 64
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, T, nh, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, T, N)) * 0.5
    us, cms = _bench(lambda *a: ref.ssd_scan(*a)[0], x, dt, A, Bm, Cm)
    vmem_kib = (128 * P * 4 + 128 * N * 4 * 2 + 128 * 128 * 4 + N * P * 4) / 1024
    out.append(f"kernel_ssd_ref_{T}t,{us:.0f},"
               f"vmem_per_block_kib={vmem_kib:.0f};compile_ms={cms:.0f}")

    # parle_update oracle (fused optimizer step)
    n = 1 << 20
    ys = [jax.random.normal(kk, (n,)) for kk in jax.random.split(key, 5)]
    us, cms = _bench(lambda *a: ref.parle_inner_update(
        *a, inv_gamma=0.01, lr=0.1, mu=0.9, alpha=0.75)[0], *ys)
    out.append(f"kernel_parle_update_1M,{us:.0f},"
               f"hbm_streams=5r3w;fused_bytes={n*4*8/1e6:.0f}MB;"
               f"compile_ms={cms:.0f}")

    # int8 sync-compression codec (quantize + error feedback: the
    # payload side of the compressed sync)
    from repro.core import compress
    c = jax.random.normal(key, (2, n // 2)).reshape(2, -1)
    c = compress.pad_to_chunk(c)
    us, cms = _bench(lambda a: compress.quantize_ef(a, "int8")[0], c)
    out.append(f"kernel_quantize_ef_1M,{us:.0f},"
               f"bytes_out_ratio=0.25;compile_ms={cms:.0f}")

    for line in out:
        print(line)
    return out


if __name__ == "__main__":
    main()
