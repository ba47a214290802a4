"""Drive the program for an LFM2-MoE configuration: the graph through the
public ``ComputationGraph`` builder and the layers ``nn/layers`` has for
it (RMS norm, gated short convolution, grouped-query attention with a q/k
norm and rotary positions, gated MLP, mixture of experts), and the
benchmark's weights put in its place. ``GraphDecodeSpec`` finds the blocks
by the kinds of these layers; the vertex names are this file's own."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def head_dim(cfg: Dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def build(cfg: Dict, hp: Dict, role: str):
    """An un-initialised ``ComputationGraph`` in the precision the
    configuration states for ``role``; ``install`` gives it weights."""
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    from deeplearning4j_tpu.nn.graph.vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (EmbeddingSequenceLayer,
                                              GatedMLP, GatedShortConvLayer,
                                              MixtureOfExpertsLayer, RMSNorm,
                                              RnnOutputLayer,
                                              SelfAttentionLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam

    V, d, eps = cfg["vocab_size"], cfg["hidden_size"], cfg["norm_eps"]
    if d != cfg["num_attention_heads"] * head_dim(cfg):
        raise ValueError("hidden_size is not heads x head size")
    upd = Adam(hp["learning_rate"], beta1=hp["beta1"], beta2=hp["beta2"],
               epsilon=hp["epsilon"])
    g = (NeuralNetConfiguration(seed=0, updater=upd, weight_init="relu",
                                activation="identity", **{
                                    k: v for k, v in cfg["precision"][role].items()
                                    if v is not None})
         .graph_builder().add_inputs("tokens")
         .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=d), "tokens"))
    h = "embed"
    for i, kind in enumerate(cfg["layer_types"]):
        b = f"l{i}_"
        if kind == "conv":
            mixer = GatedShortConvLayer(n_out=d, kernel=cfg["conv_L_cache"])
        elif kind == "full_attention":
            mixer = SelfAttentionLayer(
                n_out=d, n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"], causal=True,
                qk_norm=True, qk_norm_eps=eps, bias=False,
                rope_theta=float(cfg["rope_parameters"]["rope_theta"]))
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        if i < cfg["num_dense_layers"]:
            ffn = GatedMLP(n_hidden=cfg["intermediate_size"])
        else:
            ffn = MixtureOfExpertsLayer(
                n_experts=cfg["num_experts"],
                top_k=cfg["num_experts_per_tok"],
                n_hidden=cfg["moe_intermediate_size"],
                norm_topk=cfg["norm_topk_prob"],
                routed_scaling_factor=float(cfg["routed_scaling_factor"]))
        g = (g.add_layer(b + "norm1", RMSNorm(n_out=d, eps=eps), h)
             .add_layer(b + "mixer", mixer, b + "norm1")
             .add_vertex(b + "add1", ElementWiseVertex("add"), h, b + "mixer")
             .add_layer(b + "norm2", RMSNorm(n_out=d, eps=eps), b + "add1")
             .add_layer(b + "ffn", ffn, b + "norm2")
             .add_vertex(b + "add2", ElementWiseVertex("add"), b + "add1",
                         b + "ffn"))
        h = b + "add2"
    g = (g.add_layer("norm_f", RMSNorm(n_out=d, eps=eps), h)
         .add_layer("head", RnnOutputLayer(n_out=V, activation="softmax",
                                           loss="sparse_mcxent"), "norm_f")
         .set_outputs("head")
         .set_input_types(InputType.recurrent(1, cfg["served_context"])))
    return ComputationGraph(g.build())


def install(net, weights: Dict) -> None:
    """Put the benchmark's weights (``<vertex>/<param>``) in the net's
    place. ``init`` runs under ``eval_shape`` for its shapes and its side
    effects on the layer configurations only. No optimizer state is made:
    this family is served, and Adam's two moments of 5.3e9 parameters
    would not fit beside them."""
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
    net.opt_state = None
