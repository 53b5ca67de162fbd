"""Microbenchmark: WHERE does the fused step's 50x bandwidth gap live?

The r3 G-sweep measured ~15 GB/s effective HBM bandwidth through the fused
step (2% of v5e peak). Two suspects, each probed in isolation here:

1. **Tile padding.** TPU tiles the last two dims (e.g. (8, 128) for f32).
   The TM pools are carried as [G, C, K=8, S=4, M=12] — trailing dims 4x12
   pad to 8x128 (~21x memory inflation) UNLESS XLA's layout assignment
   collapses them. Probe: identical elementwise+reduce work on [G, C, 8, 4,
   12] vs flat [G, C, 384]; if the flat form is many times faster, the
   kernels should carry flat pools (reshape adapters at the chunk boundary).

2. **Per-stream lookup ops.** The step leans on vmapped top_k / argmax /
   argsort / sort at small shapes; if these serialize on the scalar core,
   they dominate regardless of layout. Probe: each op isolated at the
   step's exact shapes, G-batched.

Prints one JSON line per probe to stdout ({"probe": ..., "us_per_stream_tick"
: ...}); run on hardware standalone.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from rtap_tpu.utils.platform import enable_compile_cache, require_device  # noqa: E402

require_device()  # no TPU and no explicit CPU choice -> fail here

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

G, C, K, S, M = 1024, 256, 8, 4, 12
T = 16  # scan length: amortizes dispatch, matches the step's chunked shape
Ac, L = 10, 32


def bench(name: str, fn, *args) -> None:
    fn_j = jax.jit(fn)
    out = fn_j(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(3):
        out = fn_j(*args)
    jax.block_until_ready(out)
    us = (time.perf_counter() - t0) / 3 / (G * T) * 1e6
    print(json.dumps({"probe": name, "us_per_stream_tick": round(us, 3)}), flush=True)


def scanned(body):
    """Run `body(carry)` T times under lax.scan — the step's real shape."""
    def fn(x):
        def step(c, _):
            return body(c), 0.0
        return jax.lax.scan(step, x, jnp.arange(T))[0]
    return fn


def main() -> None:
    enable_compile_cache()
    print(json.dumps({"platform": jax.devices()[0].device_kind}), file=sys.stderr, flush=True)
    rng = np.random.Generator(np.random.Philox(key=(4, 4)))
    pool4 = jnp.asarray(rng.integers(-1, K * C, (G, C, K, S, M)), jnp.int32)
    perm4 = jnp.asarray(rng.random((G, C, K, S, M)), jnp.float32)
    pool2 = pool4.reshape(G, C, K * S * M)
    perm2 = perm4.reshape(G, C, K * S * M)
    ids = jnp.asarray(rng.integers(0, C, (G, Ac)), jnp.int32)
    masks = jnp.asarray(rng.integers(0, 255, (G, Ac)), jnp.int32)

    # --- probe 1: the punish/death/dendrite-shaped pass, 4-D vs flat ---
    def member(p, i, m):
        c_pre = p // K
        k_pre = p % K
        msk = jnp.where(c_pre[..., None] == i[:, None, None, None, None, :]
                        if p.ndim == 5 else c_pre[..., None] == i[:, None, None, :],
                        m[:, None, None, None, None, :] if p.ndim == 5
                        else m[:, None, None, :], 0).sum(-1)
        return (p >= 0) & (((msk >> k_pre) & 1) > 0)

    def pass4(carry):
        p, w = carry
        act = member(p, ids, masks)
        w = jnp.where(act, jnp.minimum(w + 0.01, 1.0), w)
        dead = (p >= 0) & (w <= 0.0)
        p = jnp.where(dead, -1, p)
        conn = (act & (w >= 0.5)).sum(-1)  # [G, C, K, S]
        return (p, w + 0.0 * conn[..., None])

    def pass2(carry):
        p, w = carry
        act = member(p, ids, masks)
        w = jnp.where(act, jnp.minimum(w + 0.01, 1.0), w)
        dead = (p >= 0) & (w <= 0.0)
        p = jnp.where(dead, -1, p)
        red = jnp.asarray(np.kron(np.eye(K * S, dtype=np.float32), np.ones((M, 1), np.float32)))
        conn = jax.lax.dot_general((act & (w >= 0.5)).astype(jnp.float32), red,
                                   (((2,), (0,)), ((), ())))  # [G, C, K*S]
        return (p, w + 0.0 * conn[..., None].reshape(G, C, -1)[:, :, :1])

    bench("pool_pass_4d", scanned(pass4), (pool4, perm4))
    bench("pool_pass_flat", scanned(pass2), (pool2, perm2))

    # same pass in u16 storage with f32 compute (the quantized domain cost)
    perm2_u16 = (perm2 * 65535).astype(jnp.uint16)  # rtap: domain[u16]

    def pass2_u16(carry):
        p, w16 = carry
        w = w16.astype(jnp.float32) / 65535.0
        act = member(p, ids, masks)
        w = jnp.where(act, jnp.minimum(w + 0.01, 1.0), w)
        dead = (p >= 0) & (w <= 0.0)
        p = jnp.where(dead, -1, p)
        return (p, (w * 65535).astype(jnp.uint16))  # rtap: domain[u16]

    bench("pool_pass_flat_u16", scanned(pass2_u16), (pool2, perm2_u16))

    # --- probe 2: the lookup ops at step shapes ---
    colvals = jnp.asarray(rng.random((G, C)), jnp.float32)
    bench("topk_C", scanned(
        lambda x: x + jax.lax.top_k(x, 10)[0].sum(-1, keepdims=True) * 0), colvals)

    segpot = jnp.asarray(rng.integers(0, M, (G, C, K * S)), jnp.int32)
    bench("argmax_KS", scanned(
        # rtap: allow[dtype-domain] — ×0 keeps the op in the graph, value dropped
        lambda x: x + jnp.argmax(x, axis=-1)[..., None].astype(jnp.int32) * 0), segpot)

    lperm = jnp.asarray(rng.random((G, L, M)), jnp.float32)

    def grow_sorts(x):
        ranks = jnp.argsort(jnp.argsort(x, axis=-1, stable=True), axis=-1, stable=True)
        return x + ranks * 0.0

    bench("argsort2_LM", scanned(grow_sorts), lperm)

    maskC = colvals > 0.9

    def compact(x):
        iota = jnp.arange(C, dtype=jnp.int32)
        top = jax.lax.top_k(jnp.where(x, C - iota, 0), Ac)[0]
        return x | (top.sum() > 0)

    bench("compact_ids", scanned(compact), maskC)


if __name__ == "__main__":
    main()
