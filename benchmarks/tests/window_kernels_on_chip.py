#!/usr/bin/env python3
"""The two sliding-window attention kernels alone, on the chip at
``laguna-serve-codebase``'s shapes: each against its plain twin, and its
time beside the kernel of the layers that keep the whole context.

    python benchmarks/tests/window_kernels_on_chip.py

Prints one JSON line a measurement. Forward: one whole prompt of T = 6,144
and 16,384, 64 query heads over 8 key-value heads of 128, window 512
(``flash_attention_window_fwd``), checked on three blocks of 512 queries
against dense masked attention over the keys they can see; beside it the
causal forward of 48 heads (``flash_attention_fwd``). Decode: 40 slots at
contexts of 4k-17k, a ring of 9 pages of 64 a slot and 3 layers
(``paged_attention_window_decode``) against the gather of every slot's
table under the same mask; beside it ``paged_attention_decode`` of 48
query heads over the 2 full layers' pages. Times are per call of the
jitted loop divided by its calls (a call from Python alone reads the
host's dispatch floor)."""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.pallas_attention import flash_attention
from deeplearning4j_tpu.ops.pallas_paged_attention import (
    paged_attention_decode, paged_attention_reference)

bf16 = jnp.bfloat16
W, DH, HKV = 512, 128, 8


def timed(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps, out


def forward(T):
    ks = jax.random.split(jax.random.PRNGKey(T), 3)
    q = jax.random.normal(ks[0], (1, 64, T, DH), bf16)
    k = jax.random.normal(ks[1], (1, HKV, T, DH), bf16)
    v = jax.random.normal(ks[2], (1, HKV, T, DH), bf16)
    win = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                  window=W))
    s, o = timed(win, q, k, v)
    worst = 0.0
    for r0 in (0, T // 2 - 256, T - 512):
        c0 = max(0, r0 - W)
        qb = q[:, :, r0:r0 + 512].astype(jnp.float32)
        kb = jnp.repeat(k[:, :, c0:r0 + 512], 8, 1).astype(jnp.float32)
        vb = jnp.repeat(v[:, :, c0:r0 + 512], 8, 1).astype(jnp.float32)
        sc = jnp.einsum("bhqd,bhkd->bhqk", qb, kb) / np.sqrt(DH)
        rows = (r0 + jnp.arange(512))[:, None]
        cols = (c0 + jnp.arange(kb.shape[2]))[None, :]
        sc = jnp.where((cols <= rows) & (cols > rows - W), sc, -jnp.inf)
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), vb)
        worst = max(worst, float(jnp.max(jnp.abs(
            o[:, :, r0:r0 + 512].astype(jnp.float32) - want))))
    # keys inside the windows: sum over rows of min(t + 1, W)
    keys = W * (W + 1) / 2 + (T - W) * W
    flops = 64 * keys * 2 * 2 * DH
    print(json.dumps({"kernel": "flash_attention_window_fwd", "T": T,
                      "heads": 64, "ms": s * 1e3, "max_abs_err": worst,
                      "tflops_of_needed": flops / s / 1e12}), flush=True)
    q48 = q[:, :48]
    k48, v48 = (jnp.repeat(a, 6, 1) for a in (k, v))
    full = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    s, _ = timed(full, q48, k48, v48)
    flops = 48 * (T * (T + 1) / 2) * 2 * 2 * DH
    print(json.dumps({"kernel": "flash_attention_fwd", "T": T, "heads": 48,
                      "ms": s * 1e3, "tflops_of_needed": flops / s / 1e12}),
          flush=True)


def decode(S=40, blk=64, mb=272, steps=20):
    rp = W // blk + 1
    rng = np.random.default_rng(0)
    lens = jnp.asarray(rng.integers(4096, 17408, S), jnp.int32)
    starts = jnp.maximum(lens - W, 0)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    ring_k = jax.random.normal(ks[0], (3, (S + 1) * rp, blk, HKV * DH), bf16)
    ring_v = jax.random.normal(ks[1], (3, (S + 1) * rp, blk, HKV * DH), bf16)
    tables = (jnp.arange(S, dtype=jnp.int32)[:, None] * rp
              + jnp.arange(mb, dtype=jnp.int32)[None, :] % rp)
    q = jax.random.normal(ks[2], (S, 64, 1, DH), bf16)
    got = paged_attention_decode(q, ring_k, ring_v, 1, tables, lens,
                                 starts=starts)
    # the twin gathers every slot's whole table for every query head (280 MB
    # a slot here): four slots at a time
    err = 0.0
    for s0 in range(0, S, 4):
        at = slice(s0, s0 + 4)
        want = paged_attention_reference(q[at], ring_k, ring_v, 1,
                                         tables[at], lens[at],
                                         starts=starts[at])
        err = max(err, float(jnp.max(jnp.abs(
            got[at].astype(jnp.float32) - want.astype(jnp.float32)))))

    # the pools are ARGUMENTS: closed over, they would be lowered as
    # constants of the program (5.7 GB of them for the full layers' pages)
    @jax.jit
    def win_loop(q, ring_k, ring_v):
        def body(_, q):
            for layer in range(3):
                q = q + paged_attention_decode(q, ring_k, ring_v, layer,
                                               tables, lens, starts=starts)
            return q
        return jax.lax.fori_loop(0, steps, body, q)
    s, _ = timed(win_loop, q, ring_k, ring_v)
    rows = float(jnp.minimum(lens, W).sum())
    print(json.dumps({"kernel": "paged_attention_window_decode", "slots": S,
                      "layers": 3, "ms_a_step": s / steps * 1e3,
                      "max_abs_err": err,
                      "gb_per_s_of_needed": 3 * rows * 4096 / (s / steps) / 1e9}),
          flush=True)
    del ring_k, ring_v
    make = jax.jit(lambda key: jax.random.normal(
        key, (2, S * mb + 1, blk, HKV * DH), bf16))   # no float32 copy kept
    pool_k, pool_v = make(ks[3]), make(ks[4])
    full_tables = (1 + jnp.arange(S * mb, dtype=jnp.int32)).reshape(S, mb)
    q48 = q[:, :48]

    @jax.jit
    def full_loop(q, pool_k, pool_v):
        def body(_, q):
            for layer in range(2):
                q = q + paged_attention_decode(q, pool_k, pool_v, layer,
                                               full_tables, lens)
            return q
        return jax.lax.fori_loop(0, steps, body, q)
    s, _ = timed(full_loop, q48, pool_k, pool_v)
    print(json.dumps({"kernel": "paged_attention_decode", "slots": S,
                      "layers": 2, "q_heads": 48, "ms_a_step": s / steps * 1e3,
                      "gb_per_s_of_needed":
                          2 * float(lens.sum()) * 4096 / (s / steps) / 1e9}),
          flush=True)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"window_kernels_on_chip.py needs a TPU; JAX started on "
              f"{dev.platform!r}", file=sys.stderr)
        return 3
    print(json.dumps({"device": dev.device_kind}), flush=True)
    for T in (6144, 16384):
        forward(T)
    decode()
    return 0


if __name__ == "__main__":
    sys.exit(main())
