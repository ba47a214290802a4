"""BQ/BK block-size autotune sweep for the fused flash-attention kernels.

Runs fwd+bwd causal attention on the real chip for each (BQ, BK) candidate
via the DL4J_TPU_ATTN_BQ/BK env overrides (re-imported per point in THIS
process — the override is read at trace time, so no subprocess needed),
slope-timed with the readback barrier (see bench.py::_slope_measure for
why chained timing is unusable on this rig). Prints a table plus the best
pair per config; the winners are baked into pallas_attention._blocks.

Usage:  python tools/autotune_attention.py [T] [D ...]
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def slope_time(step_fn, qkv, n_pair=(16, 64)):
    """Per-step device time via the fori_loop slope (one dynamic-n
    compiled program, readback barrier; the salt makes every timed call a
    distinct request)."""
    @jax.jit
    def many(n, salt, q, k, v):
        qs = q + salt * 1e-30
        out = jax.lax.fori_loop(0, n, lambda i, c: step_fn(c),
                                (qs, k, v))
        return sum(jnp.ravel(l)[0].astype(jnp.float32)
                   for l in jax.tree.leaves(out))

    q, k, v = qkv
    np.asarray(many(np.int32(n_pair[0]), np.float32(0), q, k, v))
    times = []
    salt = 0.0
    for n in n_pair:
        best = float("inf")
        for _ in range(3):
            salt += 1.0
            t0 = time.perf_counter()
            np.asarray(many(np.int32(n), np.float32(salt), q, k, v))
            best = min(best, time.perf_counter() - t0)
        times.append(best)
    return (times[1] - times[0]) / (n_pair[1] - n_pair[0])


def make_step(causal=True):
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention

    def step(carry):
        q, k, v = carry

        def lf(q, k, v):
            out = flash_attention(q, k, v, causal=causal)
            return jnp.sum(out * out)

        dq, dk, dv = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        return q - 1e-9 * dq, k - 1e-9 * dk, v - 1e-9 * dv
    return step


def main():
    args = [int(a) for a in sys.argv[1:]]
    T = args[0] if args else 2048
    dims = args[1:] or [64, 96, 128]
    B, H = 4, 8
    rng = np.random.default_rng(0)
    for D in dims:
        qkv = tuple(jnp.asarray(rng.normal(size=(B, H, T, D)) * 0.1,
                                jnp.float32) for _ in range(3))
        results = {}
        cands = [b for b in (128, 256, 512, 1024) if T % b == 0 and b <= T]
        for bq in [b for b in cands if b <= 512]:
            for bk in cands:
                os.environ["DL4J_TPU_ATTN_BQ"] = str(bq)
                os.environ["DL4J_TPU_ATTN_BK"] = str(bk)
                try:
                    dt = slope_time(make_step(), qkv)
                    results[(bq, bk)] = dt
                    print(f"T={T} D={D} BQ={bq:4d} BK={bk:4d}: "
                          f"{dt*1e3:7.3f} ms/step "
                          f"({B*T/dt/1e6:.2f}M tok/s)", flush=True)
                except Exception as e:
                    print(f"T={T} D={D} BQ={bq:4d} BK={bk:4d}: FAILED "
                          f"({str(e)[:120]})", flush=True)
        if results:
            (bq, bk), dt = min(results.items(), key=lambda kv: kv[1])
            print(f"==> best for T={T} D={D}: BQ={bq} BK={bk} "
                  f"({dt*1e3:.3f} ms/step)", flush=True)
    os.environ.pop("DL4J_TPU_ATTN_BQ", None)
    os.environ.pop("DL4J_TPU_ATTN_BK", None)


if __name__ == "__main__":
    main()
