"""Plain float32 ``jax.numpy`` reference of the GPT-2 forward pass, its
loss and three Adam steps, at ``highest`` matmul precision. Imports
nothing of the program. Pre-LayerNorm blocks, learned positions, causal
softmax attention with 1/sqrt(head) scaling, tanh-form GELU, an output
head that is not tied to the embedding (see the configuration's
``changed``). ``quant`` puts the reference in the program's place at the
next lower precision (the control): float8 (e4m3) fake-quantised matmul
operands.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_BLOCK = ("ln1/gain", "ln1/bias", "attn/Wq", "attn/Wk", "attn/Wv",
          "attn/Wo", "attn/b", "ln2/gain", "ln2/bias", "ff1/W", "ff1/b",
          "ff2/W", "ff2/b")


def fp8_fake_quant(x):
    """float8 e4m3 with one scale for the tensor (its largest magnitude
    maps to 448), written out in arithmetic: four significant bits, steps
    no finer than 2**-9, straight-through gradient."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, 448.0 / amax, 1.0)
    y = x * s
    _, e = jnp.frexp(y)
    step = jnp.exp2(jnp.maximum(e - 4, -9).astype(jnp.float32))
    q = jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) / s
    return x + jax.lax.stop_gradient(q - x)


CONTROL = fp8_fake_quant       # the next precision below bfloat16


def small_leaves(tree: Dict) -> Dict[str, np.ndarray]:
    """The 1-D leaves (gains, biases) on the host, in float32."""
    return {k: np.asarray(a, np.float32) for k, a in tree.items() if a.ndim == 1}


def vectors_rel_error(program: Dict, reference: Dict) -> float:
    """||program - reference|| / ||reference|| over all 1-D leaves together."""
    num = sum(float(np.sum((program[k] - reference[k]) ** 2)) for k in reference)
    den = sum(float(np.sum(reference[k] ** 2)) for k in reference)
    return (num / den) ** 0.5


def _mm(a, b, quant):
    if quant is not None:
        a, b = quant(a), quant(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _ln(x, g, b, eps=1e-5):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean((x - m) ** 2, -1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def forward(w: Dict, ids, n_head: int, n_layer: int,
            quant: Optional[Callable] = None, remat: bool = False):
    """ids [B,T] -> logits [B,T,V], float32."""
    f = lambda a: a.astype(jnp.float32)
    B, T = ids.shape
    x = f(w["embed/W"])[ids] + f(w["pos/P"])[:T][None]
    stacked = {k: jnp.stack([f(w[f"b{i}_{k}"]) for i in range(n_layer)])
               for k in _BLOCK}
    mask = jnp.tril(jnp.ones((T, T), bool))

    def heads(a):
        return a.reshape(B, T, n_head, -1).transpose(0, 2, 1, 3)

    def block(x, p):
        h = _ln(x, p["ln1/gain"], p["ln1/bias"])
        q, k, v = (heads(_mm(h, p["attn/W" + c], quant)) for c in "qkv")
        s = _mm(q, jnp.swapaxes(k, -1, -2), quant) / np.sqrt(q.shape[-1])
        s = jnp.where(mask, s, -jnp.inf)
        a = _mm(jax.nn.softmax(s, axis=-1), v, quant)
        a = a.transpose(0, 2, 1, 3).reshape(B, T, -1)
        x = x + _mm(a, p["attn/Wo"], quant) + p["attn/b"]
        h = _ln(x, p["ln2/gain"], p["ln2/bias"])
        h = _gelu(_mm(h, p["ff1/W"], quant) + p["ff1/b"])
        return x + _mm(h, p["ff2/W"], quant) + p["ff2/b"], None

    if remat:
        block = jax.checkpoint(block)
    x, _ = jax.lax.scan(block, x, stacked)
    x = _ln(x, f(w["ln_f/gain"]), f(w["ln_f/bias"]))
    return _mm(x, f(w["head/W"]), quant) + f(w["head/b"])


def rows_loss(w, ids, labels, n_head, n_layer, quant=None):
    """Sum over the rows of the summed token NLL (the program's loss is the
    mean of this over the batch)."""
    logp = jax.nn.log_softmax(forward(w, ids, n_head, n_layer, quant,
                                      remat=True), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def leaf_norms(tree: Dict) -> Dict[str, float]:
    v = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))) for k, a in t.items()})(tree)
    return {k: float(x) for k, x in v.items()}


def train_reference(w0: Dict, batches: Sequence, cfg: Dict, hp: Dict,
                    quant: Optional[Callable] = None, rows: int = 1) -> Dict:
    """Follow the first ``len(batches)`` Adam steps in float32, the batch in
    blocks of ``rows`` rows so that it fits beside nothing else. Returns
    each step's loss, the per-leaf norm of the first gradient and of the
    parameters' change after the last step."""
    nh, nl = cfg["n_head"], cfg["n_layer"]
    b1, b2, eps, lr = hp["beta1"], hp["beta2"], hp["epsilon"], hp["learning_rate"]
    grad_fn = jax.jit(jax.value_and_grad(
        lambda w, i, l: rows_loss(w, i, l, nh, nl, quant)))
    acc_fn = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g), donate_argnums=0)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(w, m, v, g, t):
        def one(w, m, v, g):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            up = lr * (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            return w - up, m, v
        out = {k: one(w[k], m[k], v[k], g[k]) for k in w}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})

    w = jax.jit(lambda t: {k: a.astype(jnp.float32) for k, a in t.items()})(w0)
    start = jax.tree.map(jnp.copy, w)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    out = {"losses": [], "grad_norms": None, "change_norms": None,
           "grad_small": None}
    for step, (ids, labels) in enumerate(batches):
        B = ids.shape[0]
        total, g = 0.0, None
        for r in range(0, B, rows):
            l, gi = grad_fn(w, jnp.asarray(ids[r:r + rows]),
                            jnp.asarray(labels[r:r + rows]))
            total += float(l)
            g = gi if g is None else acc_fn(g, gi)
        g = jax.jit(lambda t: jax.tree.map(lambda a: a / B, t))(g)
        out["losses"].append(total / B)
        if step == 0:
            out["grad_norms"] = leaf_norms(g)
            out["grad_small"] = small_leaves(g)
        w, m, v = adam(w, m, v, g, jnp.float32(step + 1))
        del g
    out["change_norms"] = leaf_norms(
        jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(w, start))
    return out


def token_gaps(w: Dict, cfg: Dict, served: List, quant=None,
               pad_to: Optional[int] = None) -> Dict:
    """For each served request (prompt ids, served tokens): run the
    reference once over prompt + tokens and return, over all served
    positions, the widest gap by which the served token's logit lies below
    the reference's best. With ``quant`` also the widest gap of the token
    the lower precision puts first at those positions (the control)."""
    nh, nl = cfg["n_head"], cfg["n_layer"]
    pad_to = pad_to or cfg["n_ctx"]
    fwd = jax.jit(lambda w, i: forward(w, i, nh, nl))
    fwd_q = jax.jit(lambda w, i: forward(w, i, nh, nl, quant)) if quant else None

    @jax.jit
    def gaps(logits, tok, pos):
        rows = logits[0][pos]                      # [n, V]
        return jnp.max(rows, -1) - jnp.take_along_axis(rows, tok[:, None], -1)[:, 0]

    widest, widest_q, n_tok, n_argmax = 0.0, 0.0, 0, 0
    for prompt, tokens in served:
        seq = np.zeros((1, pad_to), np.int32)
        full = list(prompt) + list(tokens)
        seq[0, :len(full)] = full
        pos = np.arange(len(prompt) - 1, len(full) - 1, dtype=np.int32)
        ids = jnp.asarray(seq)
        logits = fwd(w, ids)
        g = np.asarray(gaps(logits, jnp.asarray(np.asarray(tokens, np.int32)),
                            jnp.asarray(pos)))
        widest = max(widest, float(g.max()))
        n_tok += len(tokens)
        n_argmax += int((g == 0).sum())
        if fwd_q is not None:
            lq = fwd_q(w, ids)
            tq = jnp.argmax(lq[0][jnp.asarray(pos)], -1).astype(jnp.int32)
            widest_q = max(widest_q, float(np.asarray(
                gaps(logits, tq, jnp.asarray(pos))).max()))
    return {"widest_gap": widest, "control_widest_gap": widest_q,
            "tokens": n_tok, "argmax_tokens": n_argmax}
