"""Kernel microbenchmarks: wall time of the reference paths on this host
(CPU) + interpret-mode parity checks. On TPU the same harness times the
Pallas kernels (kernels/ops.py dispatch)."""
import time

import jax
import jax.numpy as jnp

from repro.core.quantization import quantize_rowwise
from repro.kernels import ops
from repro.launch.runtime import enable_compile_cache


def _time(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def rows():
    key = jax.random.key(0)
    out = []

    table = quantize_rowwise(jax.random.normal(key, (30000, 128)))
    ids = jax.random.randint(jax.random.key(1), (256, 20), 0, 30000)
    f = jax.jit(lambda tv, ts, i: ops.embedding_pool(tv, ts, i))
    us = _time(f, table.values, table.scales, ids)
    out.append(("kernel/embedding_pool_30kx128_b256", us,
                "fused int8 dequant-gather-pool (ref path on CPU)"))

    db = jax.random.randint(jax.random.key(2), (30000, 8), 0, 2**31 - 1
                            ).astype(jnp.uint32)
    q = db[:64]
    f2 = jax.jit(ops.hamming_distances)
    us = _time(f2, q, db)
    out.append(("kernel/hamming_64x30000x256b", us,
                "XOR+popcount sweep (TCAM analogue)"))

    x = jax.random.randint(jax.random.key(3), (256, 512), -127, 128
                           ).astype(jnp.int8)
    w = jax.random.randint(jax.random.key(4), (512, 512), -127, 128
                           ).astype(jnp.int8)
    sx = jnp.ones((256, 1)); sw = jnp.ones((1, 512))
    f3 = jax.jit(ops.int8_matmul)
    us = _time(f3, x, w, sx, sw)
    out.append(("kernel/int8_matmul_256x512x512", us,
                "crossbar MVM analogue (int32 accumulate)"))

    qq = jax.random.normal(key, (4, 8, 512, 64), jnp.bfloat16)
    f4 = jax.jit(lambda a: ops.flash_attention(a, a, a, causal=True))
    us = _time(f4, qq)
    out.append(("kernel/attention_4x8x512x64", us,
                "blocked online-softmax attention"))
    return out


def main():
    enable_compile_cache()
    for name, us, derived in rows():
        print(f"{name},{us:.3f},{derived}")


if __name__ == "__main__":
    main()
