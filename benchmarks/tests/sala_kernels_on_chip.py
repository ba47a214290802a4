#!/usr/bin/env python3
"""The four kernels of the sparse / linear attention hybrid alone, on the
chip at ``minicpm-sala-serve-longctx``'s shapes: each against its XLA
path, with its time.

    python benchmarks/tests/sala_kernels_on_chip.py [T ...]

Prints one JSON line a measurement. Prefill, one whole prompt of T =
16,384 and 32,768: the selection's scoring and choice (XLA,
``ops.sparse_select.chosen_mask``), ``flash_attention_sparse_fwd`` (32
query heads over 2 key-value heads of 128, 64 blocks of 64 a query past
position 8,191) checked on three blocks of 256 queries against dense
masked attention, beside the causal ``flash_attention_fwd`` of the same
heads; ``lightning_attention_fwd`` (32 heads of 128) against the chunked
XLA form. Decode, 16 slots at contexts of 16k-33k: the selected decode of
one layer (scoring, choice, ``paged_attention_sparse_decode``) against the
gather of the chosen pages, beside ``paged_attention_decode`` over every
page; ``lightning_decode`` over 6 layers' states against the XLA step.
Times are per call of a jitted function (a prefill kernel takes
milliseconds; the decode kernels are timed 20 calls a launch). Every
agreement is held to ``TOLERANCE`` (four times what the first run on the
chip read, PERF.md section 5): the script exits 1 where one is passed."""
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

from deeplearning4j_tpu.ops import pallas_linear_attention as la
from deeplearning4j_tpu.ops import sparse_select as ss
from deeplearning4j_tpu.ops.pallas_attention import (flash_attention,
                                                     flash_attention_sparse)
from deeplearning4j_tpu.ops.pallas_paged_attention import (
    paged_attention_decode, paged_attention_sparse_decode,
    paged_attention_sparse_reference)

bf16, f32 = jnp.bfloat16, jnp.float32
H, HKV, DH, BLK, SLOTS = 32, 2, 128, 64, 16
SEL = ss.Selection()
SCALE = DH ** -0.5
# the widest difference to the XLA path each kernel may show (outputs up to
# 61 in the lightning forward, where one bfloat16 step is 0.25)
TOLERANCE = {"sparse prefill": 0.05, "lightning prefill": 0.5,
             "sparse decode, one layer": 0.002,
             "lightning decode, 6 layers": 0.25, "lightning state": 0.0}
PASSED = []


def hold(what, diff):
    if not diff <= TOLERANCE[what]:
        PASSED.append((what, diff, TOLERANCE[what]))
    return diff



def timed(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps, out


def unit(key, shape):
    """Rows of unit RMS, as a q/k norm leaves them."""
    x = jax.random.normal(key, shape, f32)
    return (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))).astype(bf16)


@jax.jit
def dense_rows(qt, kt, vt, chosen, r0):
    """256 query rows from ``r0`` against every key under the mask of their
    chosen blocks, the plain way: [H, 256, DH]."""
    T = kt.shape[2]
    qb = jax.lax.dynamic_slice_in_dim(qt[0], r0, 256, axis=1).reshape(
        HKV, H // HKV, 256, DH)
    s = jnp.einsum("ghqd,gkd->ghqk", qb, kt[0],
                   preferred_element_type=f32) * SCALE
    listed = jnp.repeat(jax.lax.dynamic_slice_in_dim(
        chosen[0], r0, 256, axis=2), BLK, axis=1).transpose(0, 2, 1)
    seen = (listed > 0) & (jnp.arange(T)[None, :]
                           <= (r0 + jnp.arange(256))[:, None])
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
    return jnp.einsum("ghqk,gkd->ghqd", p.astype(bf16), vt[0],
                      preferred_element_type=f32).reshape(H, 256, DH)


def prefill(T):
    ks = jax.random.split(jax.random.PRNGKey(T), 3)
    q = unit(ks[0], (1, T, H, DH))
    k = unit(ks[1], (1, T, HKV, DH))
    v = jax.random.normal(ks[2], (1, T, HKV, DH), bf16)
    choose = jax.jit(lambda q, k: ss.chosen_mask(
        q, ss.compress_keys(k, SEL), SEL, SCALE))
    s_choose, chosen = timed(choose, q, k)
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    sparse = jax.jit(lambda q, k, v, c: flash_attention_sparse(q, k, v, c,
                                                              scale=SCALE))
    s_sparse, o = timed(sparse, qt, kt, vt, chosen)
    worst = max(float(jnp.max(jnp.abs(
        dense_rows(qt, kt, vt, chosen, r0).astype(f32)
        - o[0, :, r0:r0 + 256].astype(f32))))
        for r0 in (0, T // 2, T - 256))
    full = jax.jit(lambda q, k, v: flash_attention(
        q, jnp.repeat(k, H // HKV, 1), jnp.repeat(v, H // HKV, 1),
        causal=True))
    s_full, _ = timed(full, qt, kt, vt)
    listed = float(chosen[0, :, :, SEL.dense_len:].sum(1).mean()) \
        if T > SEL.dense_len else 0.0
    print(json.dumps({
        "what": "sparse prefill", "T": T, "choose_ms": s_choose * 1e3,
        "flash_attention_sparse_fwd_ms": s_sparse * 1e3,
        "flash_attention_fwd_32_heads_ms": s_full * 1e3,
        "blocks_listed_past_dense_len": listed,
        "worst_abs_diff_to_xla": hold("sparse prefill", worst)}), flush=True)
    ql, kl = unit(ks[0], (1, H, T, DH)), unit(ks[1], (1, H, T, DH))
    vl = jax.random.normal(ks[2], (1, H, T, DH), bf16)
    sl = la.slopes(H)
    kern = jax.jit(lambda q, k, v: la.lightning_attention_fwd(
        q, k, v, sl, scale=SCALE))
    s_kern, o = timed(kern, ql, kl, vl)
    xla = jax.jit(lambda q, k, v: la.lightning_attention_xla(
        q, k, v, sl, scale=SCALE)[0])
    s_xla, ref = timed(xla, ql, kl, vl, reps=2)
    print(json.dumps({
        "what": "lightning prefill", "T": T,
        "lightning_attention_fwd_ms": s_kern * 1e3, "xla_chunked_ms":
        s_xla * 1e3, "worst_abs_diff_to_xla": hold(
            "lightning prefill",
            float(jnp.max(jnp.abs(ref.astype(f32) - o.astype(f32))))),
        "largest_output": float(jnp.max(jnp.abs(ref.astype(f32))))}),
        flush=True)


def decode():
    rng = np.random.default_rng(0)
    pos = rng.integers(16384, 33000, SLOTS)
    mb = 33280 // BLK
    nb = SLOTS * mb + 1
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    k_pool = unit(ks[0], (2, nb, BLK, HKV, DH)).reshape(2, nb, BLK, HKV * DH)
    v_pool = jax.random.normal(ks[1], (2, nb, BLK, HKV * DH), bf16)
    tables = jnp.asarray(1 + np.arange(SLOTS * mb).reshape(SLOTS, mb),
                         jnp.int32)
    q = unit(ks[2], (SLOTS, H, 1, DH))
    comp = unit(ks[3], (SLOTS, 33280 // SEL.stride, HKV, DH))
    lens = jnp.asarray(pos + 1, jnp.int32)
    t = jnp.asarray(pos, jnp.int32)[:, None]

    def lists(q, comp):
        R = ss.block_scores(q.transpose(0, 2, 1, 3), comp, t, SEL, SCALE)
        blocks, counts = ss.chosen_lists(R, t, SEL)
        pages = jnp.take_along_axis(tables[:, None, :], blocks[:, 0], axis=2)
        return pages, counts[:, 0]

    reps = 20

    def many(fn):
        def run(q, *a):
            def body(i, q):
                return q + fn(q, *a).astype(q.dtype) * 1e-3
            return jax.lax.fori_loop(0, reps, body, q)
        return jax.jit(run)

    sel_dec = lambda q, comp: paged_attention_sparse_decode(
        q, k_pool, v_pool, 1, *lists(q, comp), lens)
    s_sel, _ = timed(many(sel_dec), q, comp)
    kern_only = lambda q, pages, counts: paged_attention_sparse_decode(
        q, k_pool, v_pool, 1, pages, counts, lens)
    pages, counts = jax.jit(lists)(q, comp)
    s_kern, _ = timed(many(kern_only), q, pages, counts)
    s_all, _ = timed(many(lambda q: paged_attention_decode(
        q, k_pool, v_pool, 1, tables, lens)), q)
    got = jax.jit(kern_only)(q, pages, counts)
    ref = jax.jit(lambda q, p, c: paged_attention_sparse_reference(
        q, k_pool, v_pool, 1, p, c, lens))(q, pages, counts)
    print(json.dumps({
        "what": "sparse decode, one layer", "slots": SLOTS,
        "mean_context": float(pos.mean()) + 1,
        "scoring_choice_and_kernel_ms": s_sel / reps * 1e3,
        "paged_attention_sparse_decode_ms": s_kern / reps * 1e3,
        "paged_attention_decode_every_page_ms": s_all / reps * 1e3,
        "keys_read_a_slot": float(((counts - 1) * BLK).mean()
                                  + (pos % BLK + 1).mean()),
        "worst_abs_diff_to_gather": hold(
            "sparse decode, one layer", float(jnp.max(jnp.abs(
                ref.astype(f32) - got.astype(f32)))))}), flush=True)

    L = 6
    pool = jax.random.normal(ks[0], (L, SLOTS + 1, H, DH, DH), f32)
    qd, kd = unit(ks[1], (SLOTS, H, DH)), unit(ks[2], (SLOTS, H, DH))
    vd = jax.random.normal(ks[3], (SLOTS, H, DH), bf16)
    active = jnp.ones((SLOTS,), bool)
    sl = la.slopes(H)

    def layers(step):
        def run(pool, q, k, v):
            out = 0.0
            for layer in range(L):
                o, pool = step(q, k, v, pool, layer, active, sl, scale=SCALE)
                out = out + o.astype(f32)
            return out, pool
        return jax.jit(run, donate_argnums=(0,))

    want, want_pool = layers(la.lightning_decode_xla)(pool + 0, qd, kd, vd)
    got, got_pool = layers(la.lightning_decode)(pool + 0, qd, kd, vd)
    diff = float(jnp.max(jnp.abs(want - got)))
    pdiff = float(jnp.max(jnp.abs(want_pool - got_pool)))
    times = {}
    for name, step in (("lightning_decode", la.lightning_decode),
                       ("xla", la.lightning_decode_xla)):
        fn = layers(step)
        p = pool + 0
        _, p = fn(p, qd, kd, vd)
        jax.block_until_ready(p)
        t0 = time.perf_counter()
        for _ in range(reps):
            _, p = fn(p, qd, kd, vd)
        jax.block_until_ready(p)
        times[name] = (time.perf_counter() - t0) / reps * 1e3
    print(json.dumps({
        "what": "lightning decode, 6 layers", "slots": SLOTS,
        "lightning_decode_ms": times["lightning_decode"],
        "xla_step_ms": times["xla"],
        "state_gb_read_and_written": 2 * L * SLOTS * H * DH * DH * 4 / 1e9,
        "worst_abs_diff_output": hold("lightning decode, 6 layers", diff),
        "worst_abs_diff_state": hold("lightning state", pdiff)}),
        flush=True)


def main() -> int:
    if jax.default_backend() != "tpu":
        print("sala_kernels_on_chip.py runs on the TPU; here: "
              + jax.default_backend(), file=sys.stderr)
        return 3
    for T in [int(a) for a in sys.argv[1:]] or (16384, 32768):
        prefill(T)
    decode()
    for what, diff, limit in PASSED:
        print(f"{what}: {diff} is over {limit}", file=sys.stderr)
    return 1 if PASSED else 0


if __name__ == "__main__":
    sys.exit(main())
