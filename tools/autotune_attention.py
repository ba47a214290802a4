"""Score-tile sweep for the fused flash-attention kernels, at the shape the
benchmark's cells run: B8 H16 D64 bfloat16, T = 1024 first.

For each tile candidate (BQ, BK) (through the DL4J_TPU_ATTN_BQ/BK env
overrides, read at trace time, so one process sweeps them all) and each
resident block (``--resident``: the rows of q, k, v a grid step keeps in
VMEM; a tile is then a step of a static loop, not of the grid) it times,
on the real chip, the forward kernel alone, each backward kernel alone and
forward + backward, and prints ``tile_schedule``'s share of the score
square beside them (on the v5e the time follows the tiles visited AND the
passes they take: PERF.md §5). Slope-timed inside one program (a
fori_loop run at two trip counts, read back once), so no per-call dispatch
is in the numbers. The winners are the defaults in
``pallas_attention._blocks``; the table of the last sweep is in PERF.md §5.

Usage:  python tools/autotune_attention.py [T ...] [--dims 64 ...]
            [--batch 8] [--heads 16] [--dtype bfloat16] [--non-causal]
            [--resident 1024 ...]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def slope_time(fn, qkv, n_pair=(16, 64)):
    """Device seconds per call of ``fn(q, k, v)`` via the fori_loop slope
    (one dynamic-n compiled program, readback barrier). Each trip feeds one
    element of its results back into q, so no trip can be hoisted or
    dropped, and nothing but the call is in the loop."""
    @jax.jit
    def many(n, q, k, v):
        def trip(_, q):
            outs = jax.tree.leaves(fn(q, k, v))
            bump = sum(o.ravel()[0].astype(jnp.float32) for o in outs)
            return q.at[0, 0, 0, 0].add((bump * 1e-9).astype(q.dtype))
        return jax.lax.fori_loop(0, n, trip, q).ravel()[0]

    np.asarray(many(np.int32(n_pair[0]), *qkv))
    times = []
    for n in n_pair:
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(many(np.int32(n), *qkv))
            best = min(best, time.perf_counter() - t0)
        times.append(best)
    return (times[1] - times[0]) / (n_pair[1] - n_pair[0])


def kernel_fns(pa, causal):
    """{label: fn(q, k, v)} over [B,H,T,D]: the forward call, each backward
    kernel alone (the other is dead code the compiler drops), and the
    whole gradient."""
    def loss(q, k, v):
        out = pa.flash_attention(q, k, v, causal=causal)
        return jnp.sum((out * out).astype(jnp.float32))

    def backward(pick):
        def fn(q, k, v):
            B, H, T, D = q.shape
            q3, k3, v3 = (a.reshape(B * H, T, D) for a in (q, k, v))
            scale = 1.0 / float(np.sqrt(D))
            o3, lse = pa._fwd(q3, k3, v3, None, causal, scale)
            return pick(pa._bwd(q3, k3, v3, None, causal, scale, o3, lse, o3))
        return fn
    return {
        "fwd": lambda q, k, v: pa.flash_attention(q, k, v, causal=causal),
        "fwd+dq": backward(lambda g: g[0]),
        "fwd+dkv": backward(lambda g: g[1:]),
        "fwd+bwd": jax.grad(loss, argnums=(0, 1, 2)),
    }


def say(line):
    print(line, flush=True)


def sweep(pa, T, D, B, H, dtype, causal, residents, out=say):
    """Time every tile candidate at one shape; returns {(R, BQ, BK): {label:
    seconds}} (R None: the module's own resident rule). Leaves the env
    overrides and the module's resident rule as it found them."""
    resident_max = pa._RESIDENT_MAX
    rng = np.random.default_rng(0)
    qkv = tuple(jnp.asarray(rng.normal(size=(B, H, T, D)) * 0.5, dtype)
                for _ in range(3))
    cands = [b for b in (128, 256, 512, 1024) if T % b == 0]
    results = {}
    for R in residents:
        for bq in [b for b in cands if b <= 512]:
            for bk in cands:
                if R is not None and (R % bq or R % bk or T % R):
                    continue
                os.environ["DL4J_TPU_ATTN_BQ"] = str(bq)
                os.environ["DL4J_TPU_ATTN_BK"] = str(bk)
                if R is not None and causal:
                    pa._RESIDENT_MAX = R
                visited, masked, total = pa.tile_schedule(T, causal)
                tag = (f"T={T} D={D} BQ={bq:4d} BK={bk:4d} resident="
                       f"{pa._resident(T, bq, bk, causal)[0]:4d}: visits "
                       f"{visited}/{total} tiles ({visited / total:.3f} of "
                       f"the square), masks {masked}")
                try:
                    row = {name: slope_time(fn, qkv) for name, fn in
                           kernel_fns(pa, causal).items()}
                except Exception as e:
                    out(f"{tag}: FAILED ({str(e)[:160]})")
                    continue
                results[(R, bq, bk)] = row
                out(f"{tag}: " + ", ".join(
                    f"{name} {dt * 1e6:8.1f} us" for name, dt in row.items()))
    os.environ.pop("DL4J_TPU_ATTN_BQ", None)
    os.environ.pop("DL4J_TPU_ATTN_BK", None)
    pa._RESIDENT_MAX = resident_max
    return results


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("T", nargs="*", type=int, default=[1024, 768, 2048])
    ap.add_argument("--dims", nargs="+", type=int, default=[64])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--non-causal", action="store_true")
    ap.add_argument("--resident", nargs="+", type=int, default=[None],
                    help="causal resident block sizes to try (default: the "
                         "module's own rule)")
    args = ap.parse_args()
    from deeplearning4j_tpu.ops import pallas_attention as pa
    causal = not args.non_causal
    say(f"device {jax.devices()[0].device_kind}; B={args.batch} "
        f"H={args.heads} {args.dtype} causal={causal}")
    for T in args.T:
        for D in args.dims:
            results = sweep(pa, T, D, args.batch, args.heads,
                            jnp.dtype(args.dtype), causal, args.resident)
            if results:
                (R, bq, bk), row = min(results.items(),
                                       key=lambda kv: kv[1]["fwd+bwd"])
                say(f"==> best for T={T} D={D}: BQ={bq} BK={bk}"
                    f"{'' if R is None else f' resident={R}'} "
                    f"({row['fwd+bwd'] * 1e6:.1f} us fwd+bwd, "
                    f"{row['fwd'] * 1e6:.1f} us fwd)")
            say(f"    default _blocks({T}, causal={causal}) = "
                f"{pa._blocks(T, causal)}")


if __name__ == "__main__":
    main()
