"""Drive the program for a GPT-2 configuration: the graph through the
public ``ComputationGraph`` builder with the vertex names
``models.transformer_lm`` uses (``TransformerDecodeSpec`` finds layers by
them), the benchmark's weights put in its place, and the readings the
comparison needs from its state."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def build(cfg: Dict, hp: Dict, role: str):
    """An un-initialised ``ComputationGraph`` in the precision the
    configuration states for ``role`` (``train`` or ``serve``); ``install``
    gives it weights."""
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    from deeplearning4j_tpu.nn.graph.vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (DenseLayer,
                                              EmbeddingSequenceLayer,
                                              LayerNormalization,
                                              PositionalEmbeddingLayer,
                                              RnnOutputLayer,
                                              SelfAttentionLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam

    V, d, L, T = cfg["vocab_size"], cfg["n_embd"], cfg["n_layer"], cfg["n_ctx"]
    upd = Adam(hp["learning_rate"], beta1=hp["beta1"], beta2=hp["beta2"],
               epsilon=hp["epsilon"])
    g = (NeuralNetConfiguration(seed=0, updater=upd, weight_init="relu",
                                activation="identity", **{
                                    k: v for k, v in cfg["precision"][role].items()
                                    if v is not None})
         .graph_builder().add_inputs("tokens")
         .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=d), "tokens")
         .add_layer("pos", PositionalEmbeddingLayer(n_out=d, max_length=T),
                    "embed"))
    h = "pos"
    for i in range(L):
        b = f"b{i}_"
        g = (g.add_layer(b + "ln1", LayerNormalization(n_out=d), h)
             .add_layer(b + "attn", SelfAttentionLayer(
                 n_out=d, n_heads=cfg["n_head"], causal=True), b + "ln1")
             .add_vertex(b + "add1", ElementWiseVertex("add"), h, b + "attn")
             .add_layer(b + "ln2", LayerNormalization(n_out=d), b + "add1")
             .add_layer(b + "ff1", DenseLayer(n_out=4 * d, activation="gelu"),
                        b + "ln2")
             .add_layer(b + "ff2", DenseLayer(n_out=d, activation="identity"),
                        b + "ff1")
             .add_vertex(b + "add2", ElementWiseVertex("add"), b + "add1",
                         b + "ff2"))
        h = b + "add2"
    g = (g.add_layer("ln_f", LayerNormalization(n_out=d), h)
         .add_layer("head", RnnOutputLayer(n_out=V, activation="softmax",
                                           loss="sparse_mcxent"), "ln_f")
         .set_outputs("head")
         .set_input_types(InputType.recurrent(1, T)))
    return ComputationGraph(g.build())


def install(net, weights: Dict) -> None:
    """Put the benchmark's weights (``<vertex>/<param>``) in the net's
    place. ``init`` runs under ``eval_shape`` for its shapes and its side
    effects on the layer configurations only: nothing is initialised on
    the device twice."""
    shapes = jax.eval_shape(lambda: (net.init().params, net.state))
    params = []
    for name, p in zip(net.vertex_names, shapes[0]):
        leaf = {}
        for k, s in p.items():
            a = weights[f"{name}/{k}"]
            if a.shape != s.shape or a.dtype != s.dtype:
                raise ValueError(f"{name}/{k}: weights {a.shape} {a.dtype}, "
                                 f"program wants {s.shape} {s.dtype}")
            leaf[k] = a
        params.append(leaf)
    used = sum(len(p) for p in params)
    if used != len(weights):
        raise ValueError(f"{len(weights)} weights made, program takes {used}")
    net.params = tuple(params)
    net.state = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes[1])
    net.opt_state = net.updater.init(net.params)


def named(net, tree) -> Dict:
    """A program tree (tuple over vertices of dicts) as ``<vertex>/<param>``."""
    return {f"{n}/{k}": a for n, p in zip(net.vertex_names, tree)
            for k, a in p.items()}


def first_gradient(net, hp: Dict) -> Dict:
    """The first gradient as the optimizer got it, from Adam's state after
    one step: m = (1 - beta1) * g."""
    return {k: s["m"].astype(jnp.float32) / (1.0 - hp["beta1"])
            for k, s in named(net, net.opt_state).items()}


def feed_dtype(cfg: Dict) -> str:
    return cfg["precision"]["train"]["dtype"]


def labels_for(ids):
    """Next-token labels: the ids shifted left (the last wraps)."""
    import numpy as np
    return np.roll(ids, -1, axis=1).astype(np.int32)


def make_batches(cfg: Dict, traffic: Dict, rng):
    """Seeded host batches of token ids; labels are the ids shifted."""
    out = []
    for _ in range(traffic["host_batches"]):
        ids = rng.integers(0, cfg["vocab_size"],
                           (traffic["batch"], traffic["seq_len"])).astype("int32")
        out.append((ids, labels_for(ids)))
    return out
