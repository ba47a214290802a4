"""Plain float32 ``jax.numpy`` reference of the DeepSeek-V3-family forward
pass (``model_type: deepseek_v3``) at ``highest`` matmul precision: no
kernels, no cache, no batching, the EXPANDED form of latent attention only
(the absorbed form is the program's business). Imports nothing of the
program. The equations (``d`` the hidden size, no bias anywhere, ``rms(x;
g) = x / sqrt(mean(x^2) + eps) * g``):

    layer l:  h = x + attn(rms(x; g1));  y = h + ffn_l(rms(h; g2))
    logits = W_head rms(y_last; g_final)
    attn(u):  q = W_q u -> heads x [q_nope | q_rope]
              [c' | r] = W_kva u;  c = rms(c'; g_kv)
              [k_nope_h | v_h] = W_kvb c  by head
              rope: pairs (x_2i, x_2i+1) rotate by pos * theta^(-2i/R), on
              q_rope of each head and on r (one key part for all heads)
              s_h,t = (q_nope_h . k_nope_h,t + q_rope_h . r_t) / sqrt(192)
              causal softmax;  o_h = sum_t p_h,t v_h,t;  out = W_o [o_h]
    dense MLP (the first ``first_k_dense_replace`` layers):
              W_2 (silu(W_1 u) * W_3 u)
    experts:  s = sigmoid(W_g u); the top-k of s + b are chosen;
              w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
              out = sum_e w_e W_2e (silu(W_1e u) * W_3e u)
                    + W_2s (silu(W_1s u) * W_3s u)       (the shared expert)

At the published widths float32 copies of all the weights are 15 GB, so the
reference walks the layers one at a time over all the sampled sequences
and upcasts from the bfloat16 weights only what one step needs. Sequences
are padded to a multiple of 256 at their end (every operation is causal or
position-wise, so the padding changes no real row) and attention takes
its queries 256 at a time, so that 17,408 positions fit: one block's
scores over 32 heads are 570 MB.

Routing is discontinuous: the routing-margin rule of
``families/lfm2_moe/reference.py`` holds here too (``token_gaps``).

``quant`` puts the reference in the program's place at the next lower
precision (the control): float8 (e4m3) fake-quantised matmul operands.
"""
from __future__ import annotations

import gc
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.gpt2.reference import CONTROL, fp8_fake_quant  # noqa: F401
from benchmarks.families.lfm2_moe.reference import (  # noqa: F401
    HIGHEST, MARGINS, PAD, _dense_ffn, _expert_add, _head, _mm, _rms,
    cell_limits, f32)

NORM_EPS = 1e-20              # the family's, in the chosen weights' sum


def _rope(x, theta):
    """x [T, H, R]: interleaved pairs (x_2i, x_2i+1) rotated in place by
    ``pos * theta^(-2i/R)``, positions 0..T-1."""
    T, H, R = x.shape
    inv = theta ** (-jnp.arange(R // 2, dtype=f32) / (R // 2))
    ang = jnp.arange(T, dtype=f32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    p = x.reshape(T, H, R // 2, 2)
    x1, x2 = p[..., 0], p[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(T, H, R)


@partial(jax.jit, static_argnames=("eps", "quant", "n_head", "dn", "dr", "dv",
                                   "rank", "theta"))
def _attn_mixer(x, g, Wq, Wkva, gkv, Wkvb, Wo, *, eps, quant, n_head, dn, dr,
                dv, rank, theta):
    """x [T, d] -> x + latent attention, expanded; T a multiple of PAD."""
    u = _rms(x, g, eps)
    T = x.shape[0]
    q = _mm(u, Wq, quant).reshape(T, n_head, dn + dr)
    cr = _mm(u, Wkva, quant)
    c = _rms(cr[:, :rank], gkv, eps)
    kv = _mm(c, Wkvb, quant).reshape(T, n_head, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    r = _rope(cr[:, None, rank:], theta)                     # [T, 1, dr]
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(r, (T, n_head, dr))], -1)
    kT = k.transpose(1, 2, 0)                                # [H, 192, T]
    v = kv[..., dn:].transpose(1, 0, 2)                      # [H, T, dv]
    scale = 1.0 / np.sqrt(dn + dr)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * PAD, PAD).transpose(1, 0, 2)
        s = _mm(qb, kT, quant) * scale                       # [H, PAD, T]
        seen = jnp.arange(T)[None, :] <= (i * PAD + jnp.arange(PAD))[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        a = _mm(jax.nn.softmax(s, axis=-1), v, quant)        # [H, PAD, dv]
        return a.transpose(1, 0, 2).reshape(PAD, n_head * dv)

    a = jax.lax.map(block, jnp.arange(T // PAD)).reshape(T, n_head * dv)
    return x + _mm(a, Wo, quant)


@partial(jax.jit, static_argnames=("eps", "quant", "top_k", "norm_topk",
                                   "scale"))
def _route(x, g, Wg, b, *, eps, quant, top_k, norm_topk, scale):
    """(u [N, d], idx [N, k], w [N, k], margin [N])."""
    u = _rms(x, g, eps)
    s = jax.nn.sigmoid(_mm(u, Wg, quant))
    top, idx = jax.lax.top_k(s + b.astype(f32), top_k + 1)
    w = jnp.take_along_axis(s, idx[:, :top_k], axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + NORM_EPS)
    return u, idx[:, :top_k], w * scale, top[:, top_k - 1] - top[:, top_k]


@partial(jax.jit, static_argnames=("quant",))
def _shared_add(acc, u, W1, W3, W2, *, quant):
    """acc [N, d] += the shared expert over every row of u."""
    return acc + _mm(jax.nn.silu(_mm(u, W1, quant)) * _mm(u, W3, quant), W2,
                     quant)


def _moe_ffn(x, w, pre, cfg, quant):
    """x [N, d] -> (x + routed experts + shared expert, margin [N])."""
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    u, idx, wts, margin = _route(
        x, w[pre + "norm2/gain"], w[pre + "ffn/Wg"], w[pre + "ffn/bias"],
        eps=cfg["rms_norm_eps"], quant=quant, top_k=k,
        norm_topk=bool(cfg["norm_topk_prob"]),
        scale=float(cfg["routed_scaling_factor"]))
    N = x.shape[0]
    idx_h, w_h = np.asarray(idx), np.asarray(wts)
    uz = jnp.concatenate([u, jnp.zeros((1, u.shape[1]), f32)])
    acc = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), f32)])
    for e in range(E):
        tok, slot = np.nonzero(idx_h == e)
        if not len(tok):
            continue
        n = max(PAD, 1 << int(np.ceil(np.log2(len(tok)))))
        rows = np.full(n, N, np.int32)
        rows[:len(tok)] = tok
        wrow = np.zeros(n, np.float32)
        wrow[:len(tok)] = w_h[tok, slot]
        acc = _expert_add(acc, uz, jnp.asarray(rows), jnp.asarray(wrow),
                          w[pre + "ffn/W1"][e], w[pre + "ffn/W3"][e],
                          w[pre + "ffn/W2"][e], quant=quant)
    out = _shared_add(acc[:N], u, w[pre + "shared/W1"], w[pre + "shared/W3"],
                      w[pre + "shared/W2"], quant=quant)
    return out, margin


def hidden_states(w: Dict, cfg: Dict, seqs: List[np.ndarray],
                  quant: Optional[Callable] = None):
    """The stack over ``seqs`` (each a 1-D array of ids): (y [N, d] the
    last layer's output over the sequences laid end to end, each padded to
    a multiple of 256; offsets [len(seqs)] where each begins; margin [N]
    the smallest routing margin over the expert layers)."""
    eps = cfg["rms_norm_eps"]
    lens = [-(-len(s) // PAD) * PAD for s in seqs]
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    ids = np.zeros(int(offs[-1]), np.int32)
    for s, o in zip(seqs, offs):
        ids[o:o + len(s)] = s
    x = w["embed/W"][jnp.asarray(ids)].astype(f32)
    margin = jnp.full((x.shape[0],), jnp.inf, f32)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"l{i}_"
        parts = [_attn_mixer(
            x[o:o + n], w[pre + "norm1/gain"], w[pre + "attn/Wq"],
            w[pre + "attn/Wkva"], w[pre + "attn/kv_gain"],
            w[pre + "attn/Wkvb"], w[pre + "attn/Wo"], eps=eps, quant=quant,
            n_head=cfg["num_attention_heads"], dn=cfg["qk_nope_head_dim"],
            dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
            rank=cfg["kv_lora_rank"], theta=float(cfg["rope_theta"]))
            for o, n in zip(offs, lens)]
        x = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        del parts
        if i < cfg["first_k_dense_replace"]:
            x = _dense_ffn(x, w[pre + "norm2/gain"], w[pre + "ffn/W1"],
                           w[pre + "ffn/W3"], w[pre + "ffn/W2"], eps=eps,
                           quant=quant)
        else:
            x, m = _moe_ffn(x, w, pre, cfg, quant)
            margin = jnp.minimum(margin, m)
    return x, offs[:-1], margin


def logits_at(w: Dict, cfg: Dict, y, rows, quant=None):
    """The head over rows ``rows`` of y: [len(rows), V] float32 (the head's
    bias is part of the program's layer and held at zero: see the
    configuration's ``changed``)."""
    return _head(y[jnp.asarray(rows)], w["norm_f/gain"], w["head/W"],
                 eps=cfg["rms_norm_eps"], quant=quant) \
        + w["head/b"].astype(f32)


def forward(w: Dict, cfg: Dict, ids, quant=None):
    """ids [T] -> logits [T, V]: the whole forward of one sequence (the
    tests' reference; the cell reads served rows only)."""
    y, _, _ = hidden_states(w, cfg, [np.asarray(ids)], quant)
    return logits_at(w, cfg, y, np.arange(len(ids)), quant)


def token_gaps(w: Dict, cfg: Dict, served: List, quant=None,
               pad_to: Optional[int] = None, limits: Optional[Dict] = None
               ) -> Dict:
    """For the served requests (prompt ids, served tokens): run the
    reference once over prompt + tokens and return, over the served
    positions whose routing margin is at least ``limits["routing_margin"]``,
    the widest gap by which the served token's logit lies below the
    reference's best (``widest_gap``), beside the share of served positions
    left out (``close_margin_share``). Where that share passes
    ``limits["close_margin_share"]`` nothing is left out and
    ``widest_gap`` is no less than ``widest_logit_gap x share / its
    limit``: the one number the harness compares then fails, whatever the
    gaps. With ``quant`` also the widest gap, at the kept positions, of the
    token the lower precision puts first (the control). (The rule and its
    printed lines are ``families/lfm2_moe/reference.py``'s.)"""
    gc.collect()               # the program's weights must be gone by now
    limits = limits if limits is not None else cell_limits(cfg)
    m, share_limit = limits["routing_margin"], limits["close_margin_share"]
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)]) for p, t in served]
    y, offs, margin = hidden_states(w, cfg, seqs)
    rows = np.concatenate([o + np.arange(len(p) - 1, len(p) + len(t) - 1)
                           for o, (p, t) in zip(offs, served)])
    toks = np.concatenate([np.asarray(t, np.int32) for _, t in served])
    logits = logits_at(w, cfg, y, rows)
    del y

    @jax.jit
    def gaps_of(logits, tok):
        return jnp.max(logits, -1) - jnp.take_along_axis(
            logits, tok[:, None], -1)[:, 0]

    g = np.asarray(gaps_of(logits, jnp.asarray(toks)))
    mg = np.asarray(margin)[rows]
    close = mg < m
    share = float(close.mean())
    keep = ~close if share <= share_limit else np.ones_like(close)
    kept_gap = float(g[keep].max()) if keep.any() else 0.0
    widest = kept_gap
    if share > share_limit:
        widest = max(kept_gap,
                     limits["widest_logit_gap"] * share / share_limit)
    print(f"[check] close_routing_margin_share: {share!r} <= "
          f"{share_limit!r} (margin under {m!r}; {int(close.sum())} of "
          f"{len(close)} served positions left out; widest gap kept "
          f"{kept_gap!r}, left out "
          f"{float(g[close].max()) if close.any() else 0.0!r}) -> "
          f"{'ok' if share <= share_limit else 'FAIL'}", flush=True)

    def by_margin(label, gaps):
        # the same reading under other margins, for whoever sets the limits
        print(f"[check] {label}: margin -> share left out, widest gap kept: "
              + "; ".join(f"{t}: {float((mg < t).mean()):.3f}, "
                          f"{float(gaps[mg >= t].max()) if (mg >= t).any() else 0.0:.4f}"
                          for t in MARGINS), flush=True)

    by_margin("program", g)
    out = {"widest_gap": widest, "kept_widest_gap": kept_gap,
           "all_widest_gap": float(g.max()), "close_margin_share": share,
           "positions_left_out": int(close.sum()),
           "smallest_margin": float(mg.min()),
           "control_widest_gap": 0.0, "tokens": int(len(toks)),
           "argmax_tokens": int((g == 0).sum()),
           "gaps": g, "margins": mg}
    if quant is not None:
        yq, _, _ = hidden_states(w, cfg, seqs, quant)
        tq = jnp.argmax(logits_at(w, cfg, yq, rows, quant), -1).astype(jnp.int32)
        gq = np.asarray(gaps_of(logits, tq))
        out["control_widest_gap"] = float(gq[keep].max()) if keep.any() else 0.0
        out["control_all_widest_gap"] = float(gq.max())
        out["control_gaps"] = gq
        by_margin("control", gq)
    return out
