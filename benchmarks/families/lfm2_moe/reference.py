"""Plain float32 ``jax.numpy`` reference of the LFM2-MoE forward pass at
``highest`` matmul precision: no kernels, no cache, no batching. Imports
nothing of the program. The equations (``d`` the hidden size, no bias
anywhere, ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``):

    layer l:  h = x + mixer_l(rms(x; g1));  y = h + ffn_l(rms(h; g2))
    logits = W_head rms(y_last; g_final)
    conv:     [B, C, X] = split3(W_in u); z = B * X;
              c_t = sum_j k[:, j] z_{t-2+j} (z_{<0} = 0); out = W_out (C * c)
    attention: q = rope(rms_head(W_q u; g_q)), k = rope(rms_head(W_k u; g_k)),
              rotate-half over the whole head, causal softmax(q k^T /
              sqrt(head)) v, query head i reading key-value head i // group
    dense MLP: W_2 (silu(W_1 u) * W_3 u)
    experts:  s = sigmoid(W_g u); the top-k of s + b are chosen;
              w = s[chosen] / (sum s[chosen] + 1e-6);
              out = sum_e w_e W_2e (silu(W_1e u) * W_3e u)

At the published widths float32 copies of all the weights are 21 GB, so the
reference walks the layers one at a time over all the sampled sequences
and upcasts from the bfloat16 weights only what one step needs (one
expert's three matrices, one mixer). Sequences are padded to a multiple of
256 at their end (every operation is causal or position-wise, so the
padding changes no real row) so that few shapes compile.

Routing is discontinuous: where the k-th and (k+1)-th biased scores lie
closer than the program's precision can tell apart, the program may
rightly choose another expert, and its logits then differ by a whole
expert's output. The reference therefore reports, per served position,
its own smallest margin ``(s + b)_(k) - (s + b)_(k+1)`` over the expert
layers; ``token_gaps`` leaves positions under ``limits["routing_margin"]``
out of the widest gap and counts them, and their share is held to
``limits["close_margin_share"]``.

``quant`` puts the reference in the program's place at the next lower
precision (the control): float8 (e4m3) fake-quantised matmul operands.
"""
from __future__ import annotations

import gc
import json
import os
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.gpt2.reference import CONTROL, fp8_fake_quant  # noqa: F401

HIGHEST = jax.lax.Precision.HIGHEST
f32 = jnp.float32
PAD = 256
MARGINS = (0.0, 0.0005, 0.001, 0.0015, 0.002, 0.003, 0.005, 0.01)


def _mm(a, b, quant):
    a, b = a.astype(f32), b.astype(f32)
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g.astype(f32)


def _rope(x, theta):
    """x [T, H, Dh], rotate-half over the whole head, positions 0..T-1."""
    T, _, Dh = x.shape
    half = Dh // 2
    inv = theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(T, dtype=f32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _conv_mixer(x, g, W_in, k, W_out, *, eps, quant):
    """x [T, d] -> x + conv mixer."""
    u = _rms(x, g, eps)
    B, C, X = jnp.split(_mm(u, W_in, quant), 3, axis=-1)
    z = B * X
    K = k.shape[1]
    zp = jnp.concatenate([jnp.zeros((K - 1, z.shape[1]), f32), z])
    T = z.shape[0]
    c = sum(zp[j:j + T] * k[:, j].astype(f32) for j in range(K))
    return x + _mm(C * c, W_out, quant)


@partial(jax.jit, static_argnames=("eps", "quant", "n_head", "n_kv", "theta"))
def _attn_mixer(x, g, Wq, Wk, Wv, Wo, gq, gk, *, eps, quant, n_head, n_kv,
                theta):
    u = _rms(x, g, eps)
    T = x.shape[0]
    q = _mm(u, Wq, quant).reshape(T, n_head, -1)
    k = _mm(u, Wk, quant).reshape(T, n_kv, -1)
    v = _mm(u, Wv, quant).reshape(T, n_kv, -1)
    q = _rope(_rms(q, gq, eps), theta)
    k = _rope(_rms(k, gk, eps), theta)
    group = n_head // n_kv
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = _mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0), quant) \
        / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = _mm(jax.nn.softmax(s, axis=-1), v.transpose(1, 0, 2), quant)
    return x + _mm(a.transpose(1, 0, 2).reshape(T, -1), Wo, quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _dense_ffn(x, g, W1, W3, W2, *, eps, quant):
    u = _rms(x, g, eps)
    return x + _mm(jax.nn.silu(_mm(u, W1, quant)) * _mm(u, W3, quant), W2,
                   quant)


@partial(jax.jit, static_argnames=("eps", "quant", "top_k", "norm_topk",
                                   "scale"))
def _route(x, g, Wg, b, *, eps, quant, top_k, norm_topk, scale):
    """(u [N, d], idx [N, k], w [N, k], margin [N])."""
    u = _rms(x, g, eps)
    s = jax.nn.sigmoid(_mm(u, Wg, quant))
    top, idx = jax.lax.top_k(s + b.astype(f32), top_k + 1)
    w = jnp.take_along_axis(s, idx[:, :top_k], axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return u, idx[:, :top_k], w * scale, top[:, top_k - 1] - top[:, top_k]


@partial(jax.jit, static_argnames=("quant",), donate_argnums=(0,))
def _expert_add(acc, u, rows, wrow, W1, W3, W2, *, quant):
    """acc [N + 1, d] += w * expert(u[rows]); padding rows point at the
    spare last row."""
    xe = u[rows]
    ye = _mm(jax.nn.silu(_mm(xe, W1, quant)) * _mm(xe, W3, quant), W2, quant)
    return acc.at[rows].add(ye * wrow[:, None])


def _moe_ffn(x, w, pre, cfg, quant):
    """x [N, d] -> (x + experts, margin [N])."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    u, idx, wts, margin = _route(
        x, w[pre + "norm2/gain"], w[pre + "ffn/Wg"], w[pre + "ffn/bias"],
        eps=cfg["norm_eps"], quant=quant, top_k=k,
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
    return acc[:N], margin


def hidden_states(w: Dict, cfg: Dict, seqs: List[np.ndarray],
                  quant: Optional[Callable] = None):
    """The stack over ``seqs`` (each a 1-D array of ids): (y [N, d] the
    last layer's output over the sequences laid end to end, each padded to
    a multiple of 256; offsets [len(seqs)] where each begins; margin [N]
    the smallest routing margin over the expert layers)."""
    eps = cfg["norm_eps"]
    lens = [-(-len(s) // PAD) * PAD for s in seqs]
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    ids = np.zeros(int(offs[-1]), np.int32)
    for s, o in zip(seqs, offs):
        ids[o:o + len(s)] = s
    x = w["embed/W"][jnp.asarray(ids)].astype(f32)
    margin = jnp.full((x.shape[0],), jnp.inf, f32)
    for i, kind in enumerate(cfg["layer_types"]):
        pre = f"l{i}_"
        parts = []
        for o, n in zip(offs, lens):
            seg = x[o:o + n]
            if kind == "conv":
                parts.append(_conv_mixer(
                    seg, w[pre + "norm1/gain"], w[pre + "mixer/W_in"],
                    w[pre + "mixer/k"], w[pre + "mixer/W_out"], eps=eps,
                    quant=quant))
            else:
                parts.append(_attn_mixer(
                    seg, w[pre + "norm1/gain"], w[pre + "mixer/Wq"],
                    w[pre + "mixer/Wk"], w[pre + "mixer/Wv"],
                    w[pre + "mixer/Wo"], w[pre + "mixer/q_gain"],
                    w[pre + "mixer/k_gain"], eps=eps, quant=quant,
                    n_head=cfg["num_attention_heads"],
                    n_kv=cfg["num_key_value_heads"],
                    theta=float(cfg["rope_parameters"]["rope_theta"])))
        x = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        if i < cfg["num_dense_layers"]:
            x = _dense_ffn(x, w[pre + "norm2/gain"], w[pre + "ffn/W1"],
                           w[pre + "ffn/W3"], w[pre + "ffn/W2"], eps=eps,
                           quant=quant)
        else:
            x, m = _moe_ffn(x, w, pre, cfg, quant)
            margin = jnp.minimum(margin, m)
    return x, offs[:-1], margin


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(y, g, W, *, eps, quant):
    return _mm(_rms(y, g, eps), W, quant)


def logits_at(w: Dict, cfg: Dict, y, rows, quant=None):
    """The head over rows ``rows`` of y: [len(rows), V] float32 (the head's
    bias is part of the program's layer and held at zero: see the
    configuration's ``changed``)."""
    return _head(y[jnp.asarray(rows)], w["norm_f/gain"], w["head/W"],
                 eps=cfg["norm_eps"], quant=quant) + w["head/b"].astype(f32)


def forward(w: Dict, cfg: Dict, ids, quant=None):
    """ids [T] -> logits [T, V]: the whole forward of one sequence (the
    tests' reference; the cell reads served rows only)."""
    y, _, _ = hidden_states(w, cfg, [np.asarray(ids)], quant)
    return logits_at(w, cfg, y, np.arange(len(ids)), quant)


def cell_limits(cfg: Dict) -> Dict:
    """The limits of the cell this configuration is served in
    (``benchmarks/limits/<cell>.json``; the configuration names the
    cell): the harness hands ``token_gaps`` no limits, and the routing
    margin is part of what is compared."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(here, "limits", cfg["limits_cell"] + ".json")) as f:
        return json.load(f)


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
    token the lower precision puts first (the control)."""
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
