"""Plain float32 ``jax.numpy`` reference of the Laguna-family forward pass
(``model_type: laguna``) at ``highest`` matmul precision: no kernels, no
cache, no batching; the sliding window is a mask on the full score block.
Imports nothing of the program. The equations (``d`` the hidden size, no
bias anywhere, ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``):

    layer l:  h = x + attn_l(rms(x; g1));  y = h + ffn_l(rms(h; g2))
    logits = W_head rms(y_last; g_final)
    attn_l(u): q = W_q u -> [H_l, 128]; k = W_k u, v = W_v u -> [8, 128];
              H_l = num_attention_heads_per_layer[l]; query head i reads
              key-value head i // (H_l / 8)
              rotation, rotate-half within the first R values of a head
              (R = 128 x partial_rotary_factor), the rest passes through:
              [x1 | x2] -> [x1 cos - x2 sin | x2 cos + x1 sin],
              angle = pos * inv_freq_i; ``default``: inv_freq_i =
              theta^(-2i/R); ``yarn``: f_i = theta^(-2i/R), ramp r_i =
              clip((i - low) / (high - low), 0, 1) with low = floor and
              high = ceil of R ln(L0 / (beta 2 pi)) / (2 ln theta) for
              beta_fast and beta_slow (L0 the original length), inv_freq_i
              = r_i f_i / factor + (1 - r_i) f_i, and cos, sin multiplied
              by attention_factor
              s = q . k / sqrt(128), causal; a ``sliding_attention``
              layer's position t sees the keys s with t - W < s <= t
              g = sigmoid(W_g u) -> [H_l]; out = W_o concat_h(g_h o_h)
    dense MLP (``mlp_layer_types`` dense): W_2 (silu(W_1 u) * W_3 u)
    experts:  s = score(W_r u) over all experts in float32 (``sigmoid``,
              or ``softmax`` over the experts: the configuration's
              ``assumed.router_score``); the top-k of s are chosen;
              w = s[chosen] / sum s[chosen] * moe_routed_scaling_factor
              out = sum_e w_e W_2e (silu(W_1e u) * W_3e u)
                    + W_2s (silu(W_1s u) * W_3s u)       (the shared expert)

Departures from the published model are the configuration file's
``assumed`` and ``changed``; in this file: the head's bias (the program's
layer carries one, held at zero) is added, and ``1e-20`` stands in the
chosen weights' sum as it does in the program's layer.

At the published widths float32 copies of all the weights are 15 GB, so the
reference walks the layers one at a time over all the sampled sequences
and upcasts from the bfloat16 weights only what one step needs. Sequences
are padded to a multiple of 256 at their end (every operation is causal or
position-wise, so the padding changes no real row) and attention takes
its queries 256 at a time, so that 17,408 positions fit: one block's
scores over 64 heads are 1.14 GB.

Routing is discontinuous: the routing-margin rule of
``families/lfm2_moe/reference.py`` holds here too (``token_gaps``; a copy,
as ``families/deepseek_v3/reference.py``'s is).

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

NORM_EPS = 1e-20              # in the chosen weights' sum


def rope_table(rp: Dict, head_dim: int):
    """(inv_freq float32 [R / 2], the factor on cos and sin, R) of one
    kind of layer from its ``rope_parameters`` entry."""
    R = int(round(head_dim * float(rp.get("partial_rotary_factor", 1))))
    theta = float(rp["rope_theta"])
    i = np.arange(R // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / R)
    if rp["rope_type"] == "default":
        return f.astype(np.float32), 1.0, R
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    L0 = float(rp["original_max_position_embeddings"])

    def pair_of(beta):       # the pair that turns beta times over L0
        return R * np.log(L0 / (beta * 2.0 * np.pi)) / (2.0 * np.log(theta))
    low = max(np.floor(pair_of(float(rp["beta_fast"]))), 0.0)
    high = min(np.ceil(pair_of(float(rp["beta_slow"]))), R - 1.0)
    r = np.clip((i - low) / (high - low), 0.0, 1.0)
    inv = r * f / float(rp["factor"]) + (1.0 - r) * f
    return inv.astype(np.float32), float(rp["attention_factor"]), R


def _rope(x, inv, factor, R):
    """x [T, H, Dh]: the first R values of each head rotated (rotate-half
    within them), positions 0..T-1; the rest passes through."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=f32)[:, None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :R // 2], x[..., R // 2:R]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., R:]], -1)


@partial(jax.jit, static_argnames=("eps", "quant", "n_head", "n_kv", "rope",
                                   "window"))
def _attn_mixer(x, g, Wq, Wk, Wv, Wo, Wg, *, eps, quant, n_head, n_kv, rope,
                window):
    """x [T, d] -> x + attention; T a multiple of PAD. ``rope`` = (inv_freq
    as a tuple, factor, R); ``window`` None or W; ``Wg`` None: no gate."""
    u = _rms(x, g, eps)
    T = x.shape[0]
    inv, factor, R = rope
    inv = np.asarray(inv, np.float32)
    q = _rope(_mm(u, Wq, quant).reshape(T, n_head, -1), inv, factor, R)
    k = _rope(_mm(u, Wk, quant).reshape(T, n_kv, -1), inv, factor, R)
    v = _mm(u, Wv, quant).reshape(T, n_kv, -1)
    group = n_head // n_kv
    kT = jnp.repeat(k, group, axis=1).transpose(1, 2, 0)      # [H, Dh, T]
    vh = jnp.repeat(v, group, axis=1).transpose(1, 0, 2)      # [H, T, Dh]
    Dh = q.shape[-1]
    scale = 1.0 / np.sqrt(Dh)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * PAD, PAD).transpose(1, 0, 2)
        s = _mm(qb, kT, quant) * scale                        # [H, PAD, T]
        rows = (i * PAD + jnp.arange(PAD))[:, None]
        cols = jnp.arange(T)[None, :]
        seen = cols <= rows
        if window is not None:
            seen = seen & (cols > rows - window)
        s = jnp.where(seen, s, -jnp.inf)
        a = _mm(jax.nn.softmax(s, axis=-1), vh, quant)        # [H, PAD, Dh]
        return a.transpose(1, 0, 2)                           # [PAD, H, Dh]

    a = jax.lax.map(block, jnp.arange(T // PAD)).reshape(T, n_head, Dh)
    if Wg is not None:
        a = a * jax.nn.sigmoid(_mm(u, Wg, quant))[:, :, None]
    return x + _mm(a.reshape(T, n_head * Dh), Wo, quant)


@partial(jax.jit, static_argnames=("eps", "quant", "top_k", "scale", "score"))
def _route(x, g, Wg, *, eps, quant, top_k, scale, score):
    """(u [N, d], idx [N, k], w [N, k], margin [N])."""
    u = _rms(x, g, eps)
    logits = _mm(u, Wg, quant)
    if score == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"router_score {score!r}")
    top, idx = jax.lax.top_k(s, top_k + 1)
    w = top[:, :top_k]
    w = w / (jnp.sum(w, -1, keepdims=True) + NORM_EPS)
    return u, idx[:, :top_k], w * scale, top[:, top_k - 1] - top[:, top_k]


@partial(jax.jit, static_argnames=("quant",))
def _shared_add(acc, u, W1, W3, W2, *, quant):
    """acc [N, d] += the shared expert over every row of u."""
    return acc + _mm(jax.nn.silu(_mm(u, W1, quant)) * _mm(u, W3, quant), W2,
                     quant)


def _moe_ffn(x, w, pre, cfg, quant):
    """x [N, d] -> (x + routed experts + shared expert, margin [N])."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    u, idx, wts, margin = _route(
        x, w[pre + "norm2/gain"], w[pre + "ffn/Wg"],
        eps=cfg["rms_norm_eps"], quant=quant, top_k=k,
        scale=float(cfg["moe_routed_scaling_factor"]),
        score=cfg["assumed"]["router_score"])
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


def _attention(x, w, pre, cfg, i, quant):
    """Layer ``i``'s attention over one padded sequence x [T, d]."""
    kind = cfg["layer_types"][i]
    inv, factor, R = rope_table(cfg["rope_parameters"][kind], cfg["head_dim"])
    return _attn_mixer(
        x, w[pre + "norm1/gain"], w[pre + "attn/Wq"], w[pre + "attn/Wk"],
        w[pre + "attn/Wv"], w[pre + "attn/Wo"],
        w[pre + "attn/Wg"] if cfg["gating"] else None,
        eps=cfg["rms_norm_eps"], quant=quant,
        n_head=cfg["num_attention_heads_per_layer"][i],
        n_kv=cfg["num_key_value_heads"],
        rope=(tuple(float(f) for f in inv), factor, R),
        window=cfg["sliding_window"] if kind == "sliding_attention" else None)


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
        parts = [_attention(x[o:o + n], w, pre, cfg, i, quant)
                 for o, n in zip(offs, lens)]
        x = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        del parts
        if cfg["mlp_layer_types"][i] == "dense":
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
        print(f"[check] {label}: margin -> positions kept, widest gap kept: "
              + "; ".join(f"{t}: {int((mg >= t).sum())}, "
                          f"{float(gaps[mg >= t].max()) if (mg >= t).any() else 0.0:.4f}"
                          for t in MARGINS), flush=True)

    by_margin("program", g)
    out = {"widest_gap": widest, "kept_widest_gap": kept_gap,
           "all_widest_gap": float(g.max()), "close_margin_share": share,
           "positions_left_out": int(close.sum()),
           "positions_kept": int(keep.sum()),
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
