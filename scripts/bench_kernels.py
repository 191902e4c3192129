#!/usr/bin/env python
"""Per-kernel attention microbenchmark.

Times the cross-attention implementations (einsum / chunked / flash)
at the shapes that dominate each BASELINE.md config's encoder — the
latent ← input step, the framework's hot op — forward and
forward+backward. Use on a real chip to pick ``--model.attention_impl``
and ``kv_chunk_size``; on CPU it validates the harness (flash runs the
Pallas kernel in interpreter mode and is expected to be slow there).

Usage: python scripts/bench_kernels.py [impl ...]
       impls: einsum chunked flash
Env:   BENCH_PLATFORM=cpu   KERNEL_SHAPES=mlm,seg   KERNEL_REPS=20
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# (name, batch, n_q, n_kv, channels, heads) — cross-attention shapes
# of the BASELINE configs. "seg" is the 262k-kv shape (32 latents ←
# 512×512 input tokens, D=16) and "seg_dec" its decoder twin (262k
# output queries ← 32 latents); "mlm2048"/"lm2048" are the seq-2048
# A/B pair at production D=16 and wide D=64 — together the harvest
# set for the flash-vs-chunked verdict (VERDICT r5 item 6).
_SHAPES = {
    "mnist": (128, 32, 784, 128, 4),
    "mlm": (64, 64, 512, 64, 4),
    "imagenet": (8, 512, 50176, 512, 4),
    "seg": (4, 32, 262144, 64, 4),
    "seg_dec": (1, 262144, 32, 64, 4),
    "lm2048": (4, 1024, 2048, 512, 8),
    "mlm2048": (16, 64, 2048, 64, 4),
}


def main():
    impls = sys.argv[1:] or ["einsum", "chunked", "flash"]
    reps = int(os.environ.get("KERNEL_REPS", "20"))
    # default harvest set = the shapes the flash-vs-chunked verdict
    # needs (262k-kv + both seq-2048 widths), heaviest last
    names = [s for s in os.environ.get(
        "KERNEL_SHAPES",
        "mnist,mlm,lm2048,mlm2048,seg,seg_dec").split(",") if s]

    import jax
    import jax.numpy as jnp

    from perceiver_tpu.cache import enable_compile_cache

    enable_compile_cache()

    want = os.environ.get("BENCH_PLATFORM")
    if want:
        jax.config.update("jax_platforms", want)

    from perceiver_tpu.ops.attention import (
        cross_attention_init,
        cross_attention_apply,
    )
    from perceiver_tpu.utils.timing import fence

    print(f"device: {jax.devices()[0]}", flush=True)
    for name in names:
        b, nq, nkv, c, h = _SHAPES[name]
        params = cross_attention_init(jax.random.key(0), c, c, h)
        q = jnp.zeros((b, nq, c), jnp.bfloat16)
        kv = jax.random.normal(jax.random.key(1), (b, nkv, c),
                               jnp.bfloat16)
        for impl in impls:
            def fwd(p, q, kv):
                return cross_attention_apply(
                    p, q, kv, num_heads=h, impl=impl).sum()

            grad = jax.jit(jax.grad(fwd))
            fj = jax.jit(fwd)
            try:
                fence(fj(params, q, kv))  # compile + first run
                t0 = time.perf_counter()
                for _ in range(reps):
                    out = fj(params, q, kv)
                fence(out)
                f_ms = (time.perf_counter() - t0) / reps * 1e3

                fence(grad(params, q, kv))  # compile + first run
                t0 = time.perf_counter()
                for _ in range(reps):
                    g = grad(params, q, kv)
                fence(g)
                fb_ms = (time.perf_counter() - t0) / reps * 1e3
                print(f"{name:9s} (B{b} q{nq} kv{nkv} c{c}) "
                      f"{impl:7s} fwd {f_ms:8.2f} ms   "
                      f"fwd+bwd {fb_ms:8.2f} ms", flush=True)
            except Exception as e:  # noqa: BLE001 — report and move on
                print(f"{name:9s} {impl:7s} FAILED: "
                      f"{type(e).__name__}: {str(e)[:120]}", flush=True)


if __name__ == "__main__":
    main()
