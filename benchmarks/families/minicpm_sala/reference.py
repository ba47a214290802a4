"""Plain float32 ``jax.numpy`` reference of the MiniCPM-SALA forward pass
(``model_type: minicpm_sala``) at ``highest`` matmul precision: no kernels,
no cache, no batching, no chunked form. Imports nothing of the program. The
equations (``d`` the hidden size, no bias anywhere, ``rms(x; g) = x /
sqrt(mean(x^2) + eps) * g``, ``a = scale_depth / sqrt(32)`` with the
PUBLISHED depth, ``Dh`` = 128):

    h0 = scale_emb E[token]
    layer l:  h = x + a mixer_l(rms(x; g1));  y = h + a mlp_l(rms(h; g2))
    mlp(u) = W_2 (silu(W_1 u) * W_3 u)
    logits = W_head (rms(y_last; g_final) / (hidden_size / dim_model_base))

    lightning-attn: q = rope(rms_head(W_q u; g_q)), k = rope(rms_head(W_k u;
              g_k)), v = W_v u, each [H, Dh]; rotate-half over the whole
              head at rope_theta; per head h, in float32, a scan over the
              positions:
                  S_t = lam_h S_{t-1} + k_t^T v_t      (S_{-1} = 0)
                  o_t = q_t S_t / sqrt(Dh)
              lam_h = exp(-2^(-8 (h + 1) / H));
              out = W_o (rms(o; g_o) * sigmoid(W_z u)), the norm over all
              H x Dh values
    minicpm4: q = rms_head(W_q u; g_q) -> [H, Dh], k = rms_head(W_k u; g_k),
              v = W_v u -> [Hkv, Dh]; no rotation; query head i reads
              key-value head i // (H / Hkv): a GROUP of H / Hkv query heads
              selection, per query position t and group g (sizes from the
              configuration's ``assumed.sparse_config``):
                  c_j = mean(k[stride j : stride j + kernel]), seen by t
                        when stride j + kernel - 1 <= t
                  p[t, h, :] = softmax_j(q[t, h] . c_j / sqrt(Dh)) over
                        the seen j;  r[t, g, j] = sum of p over the group
                  R[t, g, b] = max of r[t, g, j] over the windows j that
                        overlap block b
                  chosen: the first init_blocks blocks, the local_blocks
                        blocks ending at t's own, then the largest R (the
                        earlier block where two are equal) until topk in
                        all; every block up to t's own where there are at
                        most topk of them or t < dense_len
              o[t, h] = softmax over the keys u <= t in the chosen blocks
                        of (q[t, h] . k_u / sqrt(Dh)) . v_u
              out = W_o (o * sigmoid(W_g u)), W_g as wide as the heads

Departures from the published model are the configuration file's
``assumed`` and ``changed``; in this file: the head's bias (the program's
layer carries one, held at zero) is added.

At the published widths float32 copies of all the weights are 11 GB, so the
reference walks the layers one at a time, a sequence at a time, and upcasts
from the bfloat16 weights only what one step needs. Sequences are padded
to a multiple of 256 at their end (every operation is causal or
position-wise, so the padding changes no real row); the MLP and the sparse
attention take their rows 256 at a time (one block's scores over 32 heads
and 33,280 keys are 1.1 GB; its MLP rows 17 MB).

A selection is discontinuous, as routing is: where the last block chosen
by score and the first left out lie nearer than bfloat16 hidden states can
resolve, the program may rightly take the other block, and with a peaked
attention (``weights.py``) its output then differs by whatever weight that
block's keys carry. ``token_gaps`` reports, per served position, the
smallest MARGIN at that cut over the sparse layers and groups, as a share
of the last chosen score (inf where a position chooses by position alone).
A window that straddles two blocks gives both its score, so the two at the
cut are often EQUAL and the earlier is taken on both sides: the margin is
then the nearer of the two scores around the equal pair. Positions under
``limits["routing_margin"]`` (the name the expert cells' limits gave the
field; here the selection's) are left out of ``widest_gap`` and counted,
and a share of them over ``limits["close_margin_share"]`` fails the
comparison, as in ``families/lfm2_moe/reference.py``. The margin is read
at the sparse layers the seeded weights make bear on the logits
(``weights.py`` ``SPARSE["bearing_layers"]``, which says why the first
alone): a flip in the others moves nothing.

Two controls stand in the program's place (``CONTROLS``): ``CONTROL``, the
next lower precision (float8 e4m3 fake-quantised matmul operands), and
``LOCAL_ONLY``, full precision with every selecting position reading its
FORCED blocks alone (the first and the local ones: what a program whose
selection chose nothing, or whose kernel dropped the chosen pages, would
serve). Each must come out not correct.
"""
from __future__ import annotations

import gc
import math
from functools import partial
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.families.gpt2.reference import CONTROL, fp8_fake_quant  # noqa: F401
from benchmarks.families.lfm2_moe.reference import (  # noqa: F401
    HIGHEST, PAD, _mm, _rms, _rope, cell_limits, f32)
from benchmarks.families.minicpm_sala.weights import SPARSE


LOCAL_ONLY = "local_only"      # the second control (the module's docstring)
CONTROLS = (CONTROL, LOCAL_ONLY)
# the margins ``token_gaps`` prints a reading for, for whoever sets the limits
MARGINS = (0.0, 0.002, 0.003, 0.004, 0.005, 0.006, 0.0075, 0.01)


def residual_scale(cfg: Dict) -> float:
    return float(cfg["scale_depth"]) / math.sqrt(
        cfg["published"]["num_hidden_layers"])


def slopes(n_heads: int) -> np.ndarray:
    """``2^(-8 (h + 1) / H)``: head h decays by ``exp(-slope_h)`` a step."""
    return 2.0 ** (-8.0 * np.arange(1, n_heads + 1) / n_heads)


@partial(jax.jit, static_argnames=("eps", "quant", "a"))
def _mlp(x, g, W1, W3, W2, *, eps, quant, a):
    """x [T, d] -> x + a mlp(rms(x)); T a multiple of PAD, PAD rows at a
    time."""
    def rows(xb):
        u = _rms(xb, g, eps)
        return xb + a * _mm(jax.nn.silu(_mm(u, W1, quant))
                            * _mm(u, W3, quant), W2, quant)
    return jax.lax.map(rows, x.reshape(-1, PAD, x.shape[1])).reshape(x.shape)


@partial(jax.jit, static_argnames=("eps", "quant", "a", "n_head", "theta"))
def _lightning_mixer(x, g, Wq, Wk, Wv, Wz, Wo, gq, gk, go, *, eps, quant, a,
                     n_head, theta):
    """x [T, d] -> x + a lightning(rms(x)): the recurrence, row by row."""
    u = _rms(x, g, eps)
    T = x.shape[0]
    q = _mm(u, Wq, quant).reshape(T, n_head, -1)
    k = _mm(u, Wk, quant).reshape(T, n_head, -1)
    v = _mm(u, Wv, quant).reshape(T, n_head, -1)
    q, k = _rms(q, gq, eps), _rms(k, gk, eps)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    if quant is not None:
        q, k, v = quant(q), quant(k), quant(v)
    Dh = q.shape[-1]
    lam = jnp.exp(-jnp.asarray(slopes(n_head), f32))[:, None, None]

    def step(S, qkv):
        qt, kt, vt = qkv
        S = lam * S + kt[:, :, None] * vt[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", qt, S, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((n_head, Dh, v.shape[-1]), f32),
                        (q, k, v))
    o = (o / np.sqrt(Dh)).reshape(T, -1)
    if go is not None:
        o = _rms(o, go, eps)
    if Wz is not None:
        o = o * jax.nn.sigmoid(_mm(u, Wz, quant))
    return x + a * _mm(o, Wo, quant)


def _block_scores(r, sel):
    """r [.., J] the group scores of the compressed keys -> R [.., nb]:
    block b takes the largest r among the windows that overlap it, those
    that start in it and the ``kernel / stride - 1`` before them."""
    per = sel["block"] // sel["stride"]
    back = sel["kernel"] // sel["stride"] - 1
    nb = r.shape[-1] // per
    lead = r.shape[:-1]
    padded = jnp.concatenate(
        [jnp.full(lead + (back,), -jnp.inf, f32), r[..., :nb * per]], -1)
    cols = (np.arange(nb)[:, None] * per + np.arange(per + back)[None, :])
    return jnp.max(padded[..., cols], axis=-1)


def cut_margin(top, kk: int):
    """How near a choice of ``kk`` was: ``top`` [.., n] a row's scores in
    descending order (``kk + 2`` of them where the row has as many) -> the
    last score taken less the first left out, as a share of the former.
    Where the two are EQUAL (one straddling window scores both its blocks,
    and the earlier block is taken on both sides) nothing can flip between
    them, and the margin is the nearer of the scores around the pair. inf
    where nothing is left out by score."""
    if top.shape[-1] <= kk or kk < 2:
        return jnp.full(top.shape[:-1], jnp.inf, f32)
    last, out = top[..., kk - 1], top[..., kk]
    below = top[..., kk + 1] if top.shape[-1] > kk + 1 else -jnp.inf
    gap = jnp.where(last == out,
                    jnp.minimum(top[..., kk - 2] - last, out - below),
                    last - out) / jnp.maximum(last, 1e-30)
    return jnp.where(jnp.isfinite(gap), gap, jnp.inf)


@partial(jax.jit, static_argnames=("eps", "quant", "a", "n_head", "n_kv",
                                   "sel", "local_only"))
def _sparse_mixer(x, g, Wq, Wk, Wv, Wg, Wo, gq, gk, *, eps, quant, a, n_head,
                  n_kv, sel, local_only=False):
    """x [T, d] -> (x + a attention(rms(x)), chosen [T, n_kv, nb] bool,
    margin [T]); T a multiple of PAD and of the block; ``sel`` the
    selection's sizes as a sorted tuple of items; ``local_only``: a
    position that selects reads its forced blocks alone (a control)."""
    sel = dict(sel)
    block, kern, stride, topk = (sel["block"], sel["kernel"], sel["stride"],
                                 sel["topk"])
    u = _rms(x, g, eps)
    T = x.shape[0]
    q = _rms(_mm(u, Wq, quant).reshape(T, n_head, -1), gq, eps)
    k = _rms(_mm(u, Wk, quant).reshape(T, n_kv, -1), gk, eps)
    v = _mm(u, Wv, quant).reshape(T, n_kv, -1)
    Dh = q.shape[-1]
    group = n_head // n_kv
    scale = 1.0 / np.sqrt(Dh)
    # compressed keys: the mean of every window of ``kern`` rows, one a
    # ``stride``; a window that reaches past T is seen by no position
    J = T // stride
    starts = np.arange(J)[:, None] * stride + np.arange(kern)[None, :]
    c = jnp.where((starts < T)[:, :, None, None],
                  k[np.minimum(starts, T - 1)], 0.0).mean(axis=1)  # [J,n_kv,Dh]
    nb = T // block
    kT = jnp.repeat(k, group, axis=1).transpose(1, 2, 0)       # [H, Dh, T]
    vh = jnp.repeat(v, group, axis=1).transpose(1, 0, 2)       # [H, T, Dh]
    cT = jnp.repeat(c, group, axis=1).transpose(1, 2, 0)       # [H, Dh, J]

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * PAD, PAD).transpose(1, 0, 2)
        t = i * PAD + jnp.arange(PAD)
        # the selection of each of the PAD positions
        seen = (np.arange(J)[None, :] * stride + kern - 1) <= t[:, None]
        sc = jnp.where(seen[None], _mm(qb, cT, quant) * scale, -jnp.inf)
        p = jnp.where(seen[None], jax.nn.softmax(sc, axis=-1), 0.0)
        p = jnp.where(seen.any(-1)[None, :, None], p, 0.0)     # none seen
        r = p.reshape(n_kv, group, PAD, J).sum(axis=1)         # [n_kv,PAD,J]
        R = _block_scores(r, sel)                              # [n_kv,PAD,nb]
        b = np.arange(nb)[None, :]
        tb = (t // block)[:, None]
        forced = (b < sel["init_blocks"]) | (b > tb - sel["local_blocks"])
        rank = jnp.where(forced[None], jnp.inf, R)
        rank = jnp.where((b <= tb)[None], rank, -jnp.inf)
        kk = min(topk, nb)
        top, idx = jax.lax.top_k(rank, min(nb, kk + 2))  # lower index first
        n_chosen = jnp.minimum(kk, tb + 1)                     # [PAD, 1]
        listed = jnp.where(np.arange(idx.shape[-1])[None, None, :]
                           < n_chosen[None], idx, nb)
        chosen = (listed[..., None] == np.arange(nb)).any(axis=-2)
        if local_only:
            chosen = jnp.broadcast_to(forced[None], chosen.shape)
        dense = (t[:, None] < sel["dense_len"]) | (tb + 1 <= topk)
        chosen = (chosen | dense[None]) & (b <= tb)[None]      # [n_kv,PAD,nb]
        gap = jnp.where(dense[None, :, 0], jnp.inf,
                        cut_margin(top, kk)).min(axis=0)       # [PAD]
        # attention over the chosen blocks
        s = _mm(qb, kT, quant) * scale                         # [H, PAD, T]
        keys = jnp.repeat(chosen, block, axis=-1)              # [n_kv,PAD,T]
        keys = keys & (np.arange(T)[None, None, :] <= t[None, :, None])
        s = jnp.where(jnp.repeat(keys, group, axis=0), s, -jnp.inf)
        o = _mm(jax.nn.softmax(s, axis=-1), vh, quant)         # [H, PAD, Dh]
        return o.transpose(1, 0, 2), chosen.transpose(1, 0, 2), gap

    o, chosen, gap = jax.lax.map(rows, jnp.arange(T // PAD))
    o = o.reshape(T, n_head * Dh)
    if Wg is not None:
        o = o * jax.nn.sigmoid(_mm(u, Wg, quant))
    return (x + a * _mm(o, Wo, quant), chosen.reshape(T, n_kv, nb),
            gap.reshape(T))


def _sel_items(cfg: Dict):
    return tuple(sorted((k, int(v)) for k, v in
                        cfg["assumed"]["sparse_config"].items()))


def mixer(x, w: Dict, cfg: Dict, i: int, quant=None, local_only=False):
    """Layer ``i``'s mixer over one padded sequence x [T, d] -> (x + a
    mixer, chosen or None, margin or None)."""
    pre, eps, a = f"l{i}_", cfg["rms_norm_eps"], residual_scale(cfg)
    m = lambda n: w.get(pre + "mixer/" + n)
    if cfg["mixer_types"][i] == "minicpm4":
        if cfg["attn_use_rope"]:
            raise ValueError("the reference writes minicpm4 without rotation")
        return _sparse_mixer(
            x, w[pre + "norm1/gain"], m("Wq"), m("Wk"), m("Wv"), m("Wg"),
            m("Wo"), m("q_gain"), m("k_gain"), eps=eps, quant=quant, a=a,
            n_head=cfg["num_attention_heads"],
            n_kv=cfg["num_key_value_heads"], sel=_sel_items(cfg),
            local_only=local_only)
    return _lightning_mixer(
        x, w[pre + "norm1/gain"], m("Wq"), m("Wk"), m("Wv"), m("Wz"), m("Wo"),
        m("q_gain"), m("k_gain"), m("o_gain"), eps=eps, quant=quant, a=a,
        n_head=cfg["lightning_nh"],
        theta=float(cfg["rope_theta"]) if cfg["lightning_use_rope"]
        else None), None, None


def hidden_states(w: Dict, cfg: Dict, seqs: List[np.ndarray],
                  quant: Optional[Callable] = None, local_only: bool = False):
    """The stack over ``seqs`` (each a 1-D array of ids), a sequence at a
    time: (y [N, d] the last layer's output over the sequences laid end to
    end, each padded to a multiple of 256; offsets [len(seqs)] where each
    begins; margins [sparse layers, N] each sparse layer's selection
    margin). ``quant`` and ``local_only`` are the two controls."""
    eps, a = cfg["rms_norm_eps"], residual_scale(cfg)
    lens = [-(-len(s) // PAD) * PAD for s in seqs]
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    ys, margins = [], []
    for s, n in zip(seqs, lens):
        ids = np.zeros(n, np.int32)
        ids[:len(s)] = s
        x = w["embed/W"][jnp.asarray(ids)].astype(f32) * float(cfg["scale_emb"])
        margin = []
        for i in range(cfg["num_hidden_layers"]):
            pre = f"l{i}_"
            x, _, gap = mixer(x, w, cfg, i, quant, local_only)
            if gap is not None:
                margin.append(gap)
            x = _mlp(x, w[pre + "norm2/gain"], w[pre + "ffn/W1"],
                     w[pre + "ffn/W3"], w[pre + "ffn/W2"], eps=eps,
                     quant=quant, a=a)
        ys.append(x)
        margins.append(jnp.stack(margin))
    return (jnp.concatenate(ys) if len(ys) > 1 else ys[0], offs[:-1],
            jnp.concatenate(margins, axis=1))


@partial(jax.jit, static_argnames=("eps", "quant", "div"))
def _head(y, g, W, *, eps, quant, div):
    return _mm(_rms(y, g, eps) / div, W, quant)


def logits_at(w: Dict, cfg: Dict, y, rows, quant=None):
    """The head over rows ``rows`` of y: [len(rows), V] float32 (the head's
    bias is part of the program's layer and held at zero: see the
    configuration's ``changed``)."""
    return _head(y[jnp.asarray(rows)], w["norm_f/gain"], w["head/W"],
                 eps=cfg["rms_norm_eps"], quant=quant,
                 div=cfg["hidden_size"] / float(cfg["dim_model_base"])) \
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
    positions whose selection margin (the smallest over the sparse layers
    that bear on the logits) is at least ``limits["routing_margin"]``,
    the widest gap by which the served token's logit lies below the
    reference's best (``widest_gap``), beside the share of served positions
    left out (``close_margin_share``). Where that share passes
    ``limits["close_margin_share"]`` nothing is left out and
    ``widest_gap`` is no less than ``widest_logit_gap x share / its
    limit``: the one number the harness compares then fails, whatever the
    gaps. ``quant`` is a control or a tuple of them (``CONTROLS``): for
    each, the widest gap at the kept positions of the token it puts first
    (``control_widest_gaps`` by name; ``control_widest_gap`` their
    smallest, since every control has to come out not correct)."""
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
    mg = np.asarray(margin)[:SPARSE["bearing_layers"], rows].min(
        axis=0, initial=np.inf)
    close = mg < m
    share = float(close.mean())
    keep = ~close if share <= share_limit else np.ones_like(close)
    kept_gap = float(g[keep].max()) if keep.any() else 0.0
    widest = kept_gap
    if share > share_limit:
        widest = max(kept_gap,
                     limits["widest_logit_gap"] * share / share_limit)
    print(f"[check] close_selection_margin_share: {share!r} <= "
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
           "control_widest_gap": 0.0, "control_widest_gaps": {},
           "tokens": int(len(toks)), "argmax_tokens": int((g == 0).sum()),
           "gaps": g, "margins": mg,
           "layer_margins": np.asarray(margin)[:, rows], "control_gaps": {}}
    controls = () if quant is None else \
        quant if isinstance(quant, tuple) else (quant,)
    for control in controls:
        local = control == LOCAL_ONLY
        name = LOCAL_ONLY if local else "float8"
        q = None if local else control
        yq, _, _ = hidden_states(w, cfg, seqs, q, local_only=local)
        tq = jnp.argmax(logits_at(w, cfg, yq, rows, q), -1).astype(jnp.int32)
        del yq
        gq = np.asarray(gaps_of(logits, tq))
        out["control_widest_gaps"][name] = \
            float(gq[keep].max()) if keep.any() else 0.0
        out["control_gaps"][name] = gq
        by_margin("control " + name, gq)
    if controls:
        out["control_widest_gap"] = min(out["control_widest_gaps"].values())
    return out
