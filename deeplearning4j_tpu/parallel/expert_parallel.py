"""Expert parallelism: mixture-of-experts dispatch over a mesh axis.

NET-NEW capability beyond reference parity (SURVEY.md §2.2: the reference
has no expert parallelism). Experts are sharded over the ``expert`` mesh
axis (each device holds n_experts/n_devices expert parameter sets); tokens
are routed to their top-1 expert with capacity-bounded dispatch and exchanged
via ``all_to_all`` — the canonical TPU MoE pattern (dispatch/combine
einsums + ICI all-to-all).

This is NOT the layer a served model uses. The serving layer is
``nn/layers/gated.py`` ``MixtureOfExpertsLayer`` (sigmoid router with a
selection bias, top-k, no capacity and no dropped token, told which range
of experts it ``held``s, its matmuls in ``ops/grouped_matmul.py``): on one
chip it holds every expert and exchanges nothing. Over a mesh the exchange
will wrap that layer (tokens to the chip that holds their expert and back,
each chip computing the share ``held`` names); nothing here stands in for
it today.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from .mesh import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def expert_parallel_apply(expert_fn: Callable, mesh: Mesh,
                          axis: str = "expert", capacity_factor: float = 2.0,
                          top_k: int = 1):
    """Build ``fn(stacked_expert_params, tokens, gate_logits)``.

    - ``expert_fn(params_e, x) -> y``: one expert's computation ([T, D] in,
      [T, D'] out, shape-static).
    - ``stacked_expert_params``: leaves with leading ``n_experts`` axis,
      sharded on ``axis`` (one expert per device in this implementation:
      n_experts == mesh.shape[axis]).
    - ``tokens``: [N, D] replicated; ``gate_logits``: [N, n_experts].

    Routing is top-``top_k`` (GShard-style) with per-expert capacity
    C = ceil(capacity_factor * top_k * N / n_experts). Capacity slots are
    assigned first-choice-first: every token's choice-0 claims slots before
    any choice-1 does, so second choices absorb the leftover capacity.
    Combine weights are the chosen gate probabilities renormalized over the
    choices that actually fit — a token whose first choice overflowed is
    RE-ROUTED with full weight to its second expert (top_k >= 2 is what
    makes MoE robust to capacity overflow in practice); a token with no
    surviving choice passes through as zeros.
    """
    n = int(mesh.shape[axis])
    if not 1 <= top_k <= n:
        raise ValueError(f"top_k must be in [1, {n}], got {top_k}")

    def worker(params, tokens, gate_logits):
        params = jax.tree.map(lambda a: a[0], params)   # this device's expert
        N, D = tokens.shape
        cap = int(np.ceil(capacity_factor * top_k * N / n))
        probs = jax.nn.softmax(gate_logits, axis=-1)    # [N, E]
        top_p, top_e = jax.lax.top_k(probs, top_k)      # [N, k]
        if top_k == 1:
            # Switch-style: combine with the RAW top prob so the router gets
            # a gradient (renormalizing a single choice would be constant 1)
            gates = top_p
        else:
            gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        # slot assignment, first-choice-first (GShard): choice c's positions
        # start after ALL tokens' earlier-choice claims on that expert
        claimed = jnp.zeros((n,), jnp.int32)
        pos_ck, keep_ck = [], []
        for c in range(top_k):
            onehot = jax.nn.one_hot(top_e[:, c], n, dtype=jnp.int32)  # [N, E]
            pos = jnp.cumsum(onehot, axis=0) * onehot                 # 1-based
            pos_in_expert = jnp.sum(pos, axis=-1) - 1 + claimed[top_e[:, c]]
            pos_ck.append(pos_in_expert)
            keep_ck.append(pos_in_expert < cap)
            claimed = claimed + jnp.sum(onehot, axis=0)
        pos_k = jnp.stack(pos_ck, axis=1)               # [N, k]
        keep_k = jnp.stack(keep_ck, axis=1)             # [N, k]
        # re-route weight mass onto surviving choices (top_k >= 2): a token
        # whose first choice overflowed hands its full weight to the second.
        # Gradients still flow to the router through the surviving probs.
        live = gates * keep_k                           # [N, k]
        if top_k == 1:
            weights = live
        else:
            denom = jnp.maximum(jnp.sum(live, axis=-1, keepdims=True), 1e-9)
            weights = live / denom
        # dispatch buffer [E, cap, D] built identically on every device
        disp = jnp.zeros((n, cap, D), tokens.dtype)
        for c in range(top_k):
            disp = disp.at[top_e[:, c],
                           jnp.clip(pos_k[:, c], 0, cap - 1)].add(
                tokens * keep_k[:, c:c + 1])
        # all_to_all is unnecessary here because every device computed the
        # full dispatch; each device SELECTS its expert's slab. (With
        # token-sharded inputs this becomes a real all_to_all; the combine
        # below is the psum half of that exchange.)
        idx = jax.lax.axis_index(axis)
        my_slab = disp[idx]                              # [cap, D]
        my_out = expert_fn(params, my_slab)              # [cap, D']
        # combine: scatter my expert's outputs back to token order with the
        # re-routed weights, psum across experts
        token_idx = jnp.arange(N)
        out = jnp.zeros((N, my_out.shape[-1]), my_out.dtype)
        for c in range(top_k):
            mine = jnp.logical_and(top_e[:, c] == idx, keep_k[:, c])
            out = out.at[token_idx].add(
                my_out[jnp.clip(pos_k[:, c], 0, cap - 1)]
                * (mine * weights[:, c])[:, None])
        return jax.lax.psum(out, axis)

    inner = jax.jit(shard_map(worker, mesh=mesh,
                              in_specs=(P(axis), P(), P()), out_specs=P(),
                              check_vma=False))

    def fn(stacked_params, tokens, gate_logits):
        if gate_logits.shape[-1] != n:
            raise ValueError(
                f"gate_logits last dim ({gate_logits.shape[-1]}) must equal "
                f"the expert mesh axis size ({n}) — routing to a nonexistent "
                f"expert would silently zero those tokens")
        for leaf in jax.tree.leaves(stacked_params):
            if leaf.shape[0] != n:
                raise ValueError(
                    f"stacked expert params must have leading dim == mesh "
                    f"axis size ({n}); got {leaf.shape[0]}")
        return inner(stacked_params, tokens, gate_logits)

    return fn


def expert_sharding(mesh: Mesh, axis: str = "expert") -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def load_balancing_loss(gate_logits: jnp.ndarray, top_k: int = 1) -> jnp.ndarray:
    """Switch/GShard auxiliary load-balancing loss: E * sum_e f_e * P_e,
    where f_e is the fraction of tokens whose top-k choices include expert e
    and P_e the mean routing probability. Minimized (= top_k) at uniform
    routing (f_e = top_k/E, P_e = 1/E); add a small multiple to the training
    loss to keep experts utilized."""
    n = gate_logits.shape[-1]
    probs = jax.nn.softmax(gate_logits, axis=-1)
    _, top_e = jax.lax.top_k(probs, top_k)
    chosen = jnp.sum(jax.nn.one_hot(top_e, n), axis=1)        # [N, E]
    f = jnp.mean(chosen, axis=0)
    p = jnp.mean(probs, axis=0)
    return n * jnp.sum(f * p)
