#!/usr/bin/env python
"""Isolate where the on-chip MLM step time goes.

The first honest (fenced — utils/timing.py) bench numbers showed
~100 ms/step at batch 256 where the model's matmul FLOPs predict ~2 ms:
some op in the step is pathologically slow on the TPU. This times each
suspect in isolation, under jit, with REPS calls per timed region and a
host-fetch fence, so per-dispatch latency amortizes.

Usage: python scripts/op_diag.py [batch]
Prints one JSON line per measurement.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    import jax
    import jax.numpy as jnp

    from perceiver_tpu.cache import enable_compile_cache

    enable_compile_cache()

    from perceiver_tpu.ops.fused_ce import (
        fused_linear_cross_entropy,
        pack_positions,
    )
    from perceiver_tpu.ops.linear import linear_init
    from perceiver_tpu.ops.policy import Policy
    from perceiver_tpu.utils.timing import fence

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    seq, c, vocab = 512, 64, 10003
    n = batch * seq
    reps = 10
    pol = Policy.bf16()

    key = jax.random.key(0)
    hidden = jax.random.normal(key, (n, c), jnp.float32)
    labels = jax.random.randint(jax.random.key(1), (n,), 0, vocab)
    weight = (jax.random.uniform(jax.random.key(2), (n,)) < 0.15).astype(
        jnp.float32)
    p = 0.15
    sigma = (n * p * (1 - p)) ** 0.5
    cap = int(n * p + 6 * sigma) + 8
    lp = linear_init(jax.random.key(3), c, vocab)

    def timed(name, fn, *args, grad_of=None):
        f = jax.jit(fn)
        try:
            out = f(*args)
            fence(out)  # compile + first run
            t0 = time.perf_counter()
            for _ in range(reps):
                out = f(*args)
            fence(out)
            ms = (time.perf_counter() - t0) / reps * 1e3
            print(json.dumps({"op": name, "batch": batch,
                              "ms_per_call": round(ms, 3)}), flush=True)
        except Exception as e:  # noqa: BLE001
            print(json.dumps({"op": name, "batch": batch,
                              "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)

    # 1. the pack scatter alone
    timed("pack_positions", lambda h, y, w: pack_positions(h, y, w, cap)[0],
          hidden, labels, weight)

    # 2. fused CE on already-packed rows (no pack in the timed fn)
    hp, yp, wp, _ = jax.jit(
        lambda h, y, w: pack_positions(h, y, w, cap))(hidden, labels, weight)
    timed("fused_ce_fwd(packed_rows)",
          lambda a, h, y, w: fused_linear_cross_entropy(
              a, h, y, w, chunk_size=min(8192, cap), policy=pol),
          lp, hp, yp, wp)
    timed("fused_ce_grad(packed_rows)",
          jax.grad(lambda a, h, y, w: fused_linear_cross_entropy(
              a, h, y, w, chunk_size=min(8192, cap), policy=pol)),
          lp, hp, yp, wp)

    # 3. pack + CE together (= the loss path minus the encoder)
    timed("pack+fused_ce_fwd",
          lambda a, h, y, w: fused_linear_cross_entropy(
              a, *pack_positions(h, y, w, cap)[:3],
              chunk_size=min(8192, cap), policy=pol),
          lp, hidden, labels, weight)

    # 4. a bare big matmul chain as a chip-health yardstick
    x = jnp.ones((4096, 4096), jnp.bfloat16)

    def chain(x):
        # divide by a same-dtype scalar: a numpy f32 scalar is not
        # weak-typed, so dividing by jnp.sqrt(jnp.float32(...)) would
        # promote x to f32 after the first iteration and run 19 of the
        # 20 matmuls at the MXU's f32 rate — misreporting bf16 health
        inv = (1.0 / jnp.sqrt(4096.0)).astype(x.dtype)
        for _ in range(20):
            x = x @ x
            x = x * inv
        return x

    t0 = time.perf_counter()
    y = jax.jit(chain)(x)
    fence(y)
    t0 = time.perf_counter()
    y = jax.jit(chain)(x)
    fence(y)
    dt = time.perf_counter() - t0
    print(json.dumps({"op": "matmul_chain20_4096",
                      "tflops": round(20 * 2 * 4096**3 / dt / 1e12, 1),
                      "ms_per_call": round(dt * 1e3, 1)}), flush=True)

    # 5. cumsum alone (the other non-matmul candidate in the pack)
    timed("cumsum_131k", lambda w: jnp.cumsum((w > 0).astype(jnp.int32)),
          weight)


if __name__ == "__main__":
    main()
