"""Ring attention: sequence/context parallelism over a mesh axis.

NET-NEW capability beyond reference parity (SURVEY.md §5.7 records that the
reference has NO attention and no context parallelism; the survey directs
that the sequence dimension be a shardable mesh axis). This module provides
the TPU-idiomatic long-context primitive: the sequence is sharded across a
``seq`` mesh axis, each device holds one Q/K/V block, and K/V blocks rotate
around the ring via ``jax.lax.ppermute`` while a numerically-stable online
softmax (running max + rescaled partial sums, the FlashAttention recurrence)
accumulates the output — peak memory per device is O(T/n) instead of O(T),
and the permute traffic rides ICI neighbor links.

Public surface:
- ``attention(q, k, v, causal=...)`` — plain single-device reference.
- ``ring_attention_sharded(mesh, axis, ...)`` — builds the shard_map'd
  long-context attention over the mesh; output is bitwise-comparable (up to
  float tolerance) with the single-device version on the gathered sequence.
- ``SelfAttentionLayer`` (nn/layers/attention.py) uses ``attention`` on one
  chip; swap in the sharded variant for long sequences.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from .mesh import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_update(acc, m, l, q, k, v, scale, mask=None):
    """One block of the online-softmax recurrence (FlashAttention-style):
    q [B,H,Tq,D], k/v [B,H,Tk,D]; carry (acc [B,H,Tq,D], m [B,H,Tq],
    l [B,H,Tq])."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * jnp.asarray(scale, q.dtype)
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # exp(-inf - -inf) guards: fully-masked blocks contribute nothing
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(s), s - safe_m[..., None], -jnp.inf))
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return acc_new, m_new, l_new


def attention(q, k, v, *, causal: bool = False,
              scale: Optional[float] = None, key_mask=None,
              window: Optional[int] = None):
    """Plain softmax attention, [B,H,T,D] in/out (single-device reference
    semantics for the ring version). ``key_mask`` [B,Tk] excludes padded
    timesteps as keys (large-negative rather than -inf so a fully-masked
    query row yields a uniform distribution instead of NaN). ``window``
    (causal only): query t sees the keys s with t - window < s <= t."""
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * jnp.asarray(scale, q.dtype)
    if key_mask is not None:
        s = jnp.where(jnp.asarray(key_mask, q.dtype)[:, None, None, :] > 0,
                      s, -1e30)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((tq, tk), bool),
                                    k=tk - tq - window)
        s = jnp.where(mask, s, -1e30 if key_mask is not None else -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _ring_body(q, k0, v0, axis, n, causal, scale, t_local):
    """Executes on each device inside shard_map: local q stays, k/v rotate
    n-1 hops; online softmax accumulates across blocks."""
    idx = jax.lax.axis_index(axis)
    B, H, Tq, D = q.shape

    def step(carry, j):
        # lax.scan (not fori_loop): scan has a reverse-mode rule, so the ring
        # is TRAINABLE — jax.grad re-runs the ring backwards with the same
        # ppermute traffic pattern
        acc, m, l, k, v = carry
        src = (idx - j) % n          # which device's k/v block we hold now
        mask = None
        if causal:
            q_pos = idx * t_local + jnp.arange(Tq)[:, None]       # [Tq,1]
            k_pos = src * t_local + jnp.arange(k.shape[2])[None]  # [1,Tk]
            mask = (k_pos <= q_pos)[None, None]                   # [1,1,Tq,Tk]
        acc, m, l = _block_update(acc, m, l, q, k, v, scale, mask)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k = jax.lax.ppermute(k, axis, perm)
        v = jax.lax.ppermute(v, axis, perm)
        return (acc, m, l, k, v), None

    acc = jnp.zeros(q.shape, q.dtype)
    m = jnp.full((B, H, Tq), -jnp.inf, q.dtype)
    l = jnp.zeros((B, H, Tq), q.dtype)
    (acc, m, l, _, _), _ = jax.lax.scan(step, (acc, m, l, k0, v0),
                                        jnp.arange(n))
    return acc / jnp.maximum(l, 1e-20)[..., None]


# ------------------------------------------------------------- fused ring
# The XLA ring body above materializes the local [Tq,Tk] score block in HBM
# every hop; the fused ring folds each hop through the carry-emitting Pallas
# kernel (ops/pallas_attention.flash_block_update) so per-hop HBM traffic is
# O(t_local * D). With EQUAL per-device blocks the causal relation between
# the resident q block and the visiting k/v block is one of exactly three
# cases — fully visible (src < idx), diagonal (src == idx), fully hidden
# (src > idx) — so a lax.switch over non-causal / causal / skip kernels
# covers causality with no global-offset plumbing inside the kernel.
# Backward is the standard ring-attention decomposition: FlashAttention-2
# per-hop contributions with the GLOBAL logsumexp, dk/dv accumulators
# rotating WITH their k/v blocks (after n hops they land back home).


def _ring_fused_fwd(q3, k3, v3, axis, n, causal, scale):
    from ..ops.pallas_attention import flash_block_update
    # axis_index only when causality needs it: a dead PartitionId survives
    # to SPMD partitioning on older XLA CPU backends and aborts the compile
    idx = jax.lax.axis_index(axis) if causal else None
    BH, t, D = q3.shape
    f32 = jnp.float32
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, j):
        acc, m, l, k, v = carry
        src = (idx - j) % n if causal else None
        ops = (acc, m, l)

        def diag(o):
            return flash_block_update(*o, q3, k, v, causal=True, scale=scale)

        def full(o):
            return flash_block_update(*o, q3, k, v, causal=False, scale=scale)

        def skip(o):
            return o

        if causal:
            branch = jnp.where(src == idx, 0, jnp.where(src < idx, 1, 2))
            acc, m, l = jax.lax.switch(branch, [diag, full, skip], ops)
        else:
            acc, m, l = full(ops)
        k = jax.lax.ppermute(k, axis, perm)
        v = jax.lax.ppermute(v, axis, perm)
        return (acc, m, l, k, v), None

    acc = jnp.zeros((BH, t, D), f32)
    m = jnp.full((BH, 1, t), -1e30, f32)     # per-query rows, the kernels'
    l = jnp.zeros((BH, 1, t), f32)           # layout (pallas_attention._scores)
    (acc, m, l, _, _), _ = jax.lax.scan(step, (acc, m, l, k3, v3),
                                        jnp.arange(n))
    # epsilon guard matching the XLA ring body: a row that accumulated no
    # probability mass (a future key_mask / all-hops-skipped case) degrades
    # to zeros instead of NaN
    o3 = (acc / jnp.maximum(l, 1e-20).reshape(BH, t, 1)).astype(q3.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return o3, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_fused(q3, k3, v3, axis, n, causal, scale):
    o3, _ = _ring_fused_fwd(q3, k3, v3, axis, n, causal, scale)
    return o3


def _ring_fused_fwd_rule(q3, k3, v3, axis, n, causal, scale):
    o3, lse = _ring_fused_fwd(q3, k3, v3, axis, n, causal, scale)
    return o3, (q3, k3, v3, o3, lse)


def _ring_fused_bwd_rule(axis, n, causal, scale, res, do3):
    from ..ops.pallas_attention import flash_block_bwd
    q3, k3, v3, o3, lse = res
    idx = jax.lax.axis_index(axis) if causal else None
    f32 = jnp.float32
    perm = [(i, (i + 1) % n) for i in range(n)]
    zero = (jnp.zeros(q3.shape, f32),) + 2 * (jnp.zeros(k3.shape, f32),)

    def step(carry, j):
        dq, dk, dv, k, v = carry
        src = (idx - j) % n if causal else None

        def diag(ops):
            out = flash_block_bwd(q3, *ops, o3, lse, do3, causal=True,
                                  scale=scale)
            return tuple(x.astype(f32) for x in out)

        def full(ops):
            out = flash_block_bwd(q3, *ops, o3, lse, do3, causal=False,
                                  scale=scale)
            return tuple(x.astype(f32) for x in out)

        def skip(ops):
            return zero

        if causal:
            branch = jnp.where(src == idx, 0, jnp.where(src < idx, 1, 2))
            dq_c, dk_c, dv_c = jax.lax.switch(branch, [diag, full, skip],
                                              (k, v))
        else:
            dq_c, dk_c, dv_c = full((k, v))
        dq = dq + dq_c
        dk = dk + dk_c
        dv = dv + dv_c
        # dk/dv accumulators travel WITH their k/v blocks: after n hops
        # each lands on the device that owns its block
        k, v, dk, dv = (jax.lax.ppermute(x, axis, perm)
                        for x in (k, v, dk, dv))
        return (dq, dk, dv, k, v), None

    dq = jnp.zeros(q3.shape, f32)
    dk = jnp.zeros(k3.shape, f32)
    dv = jnp.zeros(v3.shape, f32)
    (dq, dk, dv, _, _), _ = jax.lax.scan(step, (dq, dk, dv, k3, v3),
                                         jnp.arange(n))
    return (dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype))


_ring_fused.defvjp(_ring_fused_fwd_rule, _ring_fused_bwd_rule)


def _ring_body_fused(q, k0, v0, axis, n, causal, scale):
    B, H, t, D = q.shape
    o3 = _ring_fused(q.reshape(B * H, t, D), k0.reshape(B * H, t, D),
                     v0.reshape(B * H, t, D), axis, n, causal, scale)
    return o3.reshape(B, H, t, D)


def ring_attention_sharded(mesh: Mesh, axis: str = "seq", *,
                           causal: bool = False,
                           scale: Optional[float] = None,
                           use_fused: Optional[bool] = None):
    """Build a jitted ring-attention fn over ``mesh``: inputs [B,H,T,D] with
    T sharded on ``axis`` (T must divide evenly); output sharded the same.

        fn = ring_attention_sharded(mesh, "seq", causal=True)
        out = fn(q, k, v)     # q,k,v sharded NamedSharding(mesh, P(None,None,"seq"))

    ``use_fused``: None (default) probes fused_ring_applicable and takes
    the Pallas carry-emitting hop kernels when the local block qualifies
    (O(t_local*D) HBM traffic per hop instead of the XLA body's [Tq,Tk]
    score materialization); True forces, False opts out.
    """
    from ..ops.pallas_attention import fused_ring_applicable
    n = int(mesh.shape[axis])

    def fn(q, k, v):
        sc = float(scale) if scale is not None else 1.0 / float(np.sqrt(q.shape[-1]))
        t_local = q.shape[2] // n
        fused = use_fused
        if fused is None:
            fused = fused_ring_applicable(t_local, q.shape[-1], q.dtype)
        elif fused and not (t_local > 0 and t_local % 128 == 0
                            and (q.shape[-1] % 128 == 0
                                 or q.shape[-1] in (64, 96))):
            # validate the explicit opt-in HERE, at the misuse site — the
            # alternative is a confusing 'T not a multiple of 128'
            # ValueError from deep inside the Pallas kernel's block sizing
            # (ops/pallas_attention._blocks) at trace time. Only the HARD
            # shape constraints are enforced: an explicit True is allowed
            # to force the interpret path on a non-TPU backend (the
            # multichip dryrun and the CPU parity tests do exactly that),
            # which the fused_ring_applicable auto-probe would refuse.
            raise ValueError(
                f"use_fused=True, but the fused ring-hop kernels cannot "
                f"serve this call: t_local = T/ring_size = "
                f"{q.shape[2]}/{n} = {t_local} must be a positive "
                f"multiple of 128 (the TPU lane dim), with head dim "
                f"{q.shape[-1]} in (64, 96, any multiple of 128). Pass "
                f"use_fused=None to auto-fallback to the XLA ring body "
                f"instead")
        if fused:
            body = functools.partial(_ring_body_fused, axis=axis, n=n,
                                     causal=causal, scale=sc)
        else:
            body = functools.partial(_ring_body, axis=axis, n=n,
                                     causal=causal, scale=sc,
                                     t_local=t_local)
        spec = P(None, None, axis, None)
        sharded = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                            out_specs=spec, check_vma=False)
        return sharded(q, k, v)

    return jax.jit(fn)


def sequence_sharding(mesh: Mesh, axis: str = "seq") -> NamedSharding:
    """Sharding for [B,H,T,D] tensors with the time axis on ``axis``."""
    return NamedSharding(mesh, P(None, None, axis, None))
