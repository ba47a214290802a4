"""Drive the program for a MiniCPM-SALA configuration (``model_type:
minicpm_sala``): the graph through the public ``ComputationGraph`` builder
and the layers ``nn/layers`` has for it (RMS norm; ``SelfAttentionLayer``
with a q/k norm, no rotation, a full-width output gate and a block-sparse
selection for a ``minicpm4`` mixer; ``LightningAttentionLayer`` for a
``lightning-attn`` mixer; gated MLP), the model's three scalings as
``ScaleVertex``es, and the benchmark's weights put in its place.
``GraphDecodeSpec`` finds the blocks by the kinds of these layers; the
vertex names are this file's own."""
from __future__ import annotations

import math
from typing import Dict

from benchmarks.families.lfm2_moe.build import install  # noqa: F401



def residual_scale(cfg: Dict) -> float:
    """``scale_depth / sqrt(num_hidden_layers)`` with the PUBLISHED depth:
    cutting the stack does not change what a layer adds."""
    return float(cfg["scale_depth"]) / math.sqrt(
        cfg["published"]["num_hidden_layers"])


def head_scale(cfg: Dict) -> float:
    """The final norm's output is divided by ``hidden_size /
    dim_model_base`` before the head."""
    return float(cfg["dim_model_base"]) / float(cfg["hidden_size"])


def mixer(cfg: Dict, i: int):
    """Layer ``i``'s sequence mixer from the configuration's keys."""
    from deeplearning4j_tpu.nn.layers import (LightningAttentionLayer,
                                              SelfAttentionLayer)
    kind = cfg["mixer_types"][i]
    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    if kind == "minicpm4":
        return SelfAttentionLayer(
            n_out=d, n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_size=cfg["head_dim"],
            causal=True, bias=bool(cfg["attention_bias"]),
            qk_norm=bool(cfg["qk_norm"]), qk_norm_eps=eps,
            rope_theta=float(cfg["rope_theta"]) if cfg["attn_use_rope"]
            else None,
            out_gate=bool(cfg["attn_use_output_gate"]),
            sparse=dict(cfg["assumed"]["sparse_config"]))
    if kind == "lightning-attn":
        if cfg["lightning_nkv"] != cfg["lightning_nh"] or not (
                cfg["qk_norm"] and cfg["lightning_use_rope"]
                and cfg["use_output_norm"] and cfg["use_output_gate"]):
            raise ValueError(
                "LightningAttentionLayer is the published form: one "
                "key-value head a query head, q/k norm, rotation, output "
                "norm and output gate")
        return LightningAttentionLayer(
            n_out=d, n_heads=cfg["lightning_nh"],
            head_size=cfg["lightning_head_dim"], norm_eps=eps,
            rope_theta=float(cfg["rope_theta"]))
    raise ValueError(f"unknown mixer type {kind!r}")


def build(cfg: Dict, hp: Dict, role: str):
    """An un-initialised ``ComputationGraph`` in the precision the
    configuration states for ``role``; ``install`` gives it weights."""
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    from deeplearning4j_tpu.nn.graph.vertices import (ElementWiseVertex,
                                                      ScaleVertex)
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (EmbeddingSequenceLayer,
                                              GatedMLP, RMSNorm,
                                              RnnOutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam

    V, d, eps = cfg["vocab_size"], cfg["hidden_size"], cfg["rms_norm_eps"]
    n = cfg["num_hidden_layers"]
    if len(cfg["mixer_types"]) != n:
        raise ValueError(f"mixer_types has {len(cfg['mixer_types'])} "
                         f"entries for {n} layers")
    a = residual_scale(cfg)
    upd = Adam(hp["learning_rate"], beta1=hp["beta1"], beta2=hp["beta2"],
               epsilon=hp["epsilon"])
    g = (NeuralNetConfiguration(seed=0, updater=upd, weight_init="relu",
                                activation="identity", **{
                                    k: v for k, v in cfg["precision"][role].items()
                                    if v is not None})
         .graph_builder().add_inputs("tokens")
         .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=d), "tokens")
         .add_vertex("embed_s", ScaleVertex(float(cfg["scale_emb"])), "embed"))
    h = "embed_s"
    for i in range(n):
        b = f"l{i}_"
        g = (g.add_layer(b + "norm1", RMSNorm(n_out=d, eps=eps), h)
             .add_layer(b + "mixer", mixer(cfg, i), b + "norm1")
             .add_vertex(b + "mixer_s", ScaleVertex(a), b + "mixer")
             .add_vertex(b + "add1", ElementWiseVertex("add"), h,
                         b + "mixer_s")
             .add_layer(b + "norm2", RMSNorm(n_out=d, eps=eps), b + "add1")
             .add_layer(b + "ffn", GatedMLP(n_hidden=cfg["intermediate_size"]),
                        b + "norm2")
             .add_vertex(b + "ffn_s", ScaleVertex(a), b + "ffn")
             .add_vertex(b + "add2", ElementWiseVertex("add"), b + "add1",
                         b + "ffn_s"))
        h = b + "add2"
    g = (g.add_layer("norm_f", RMSNorm(n_out=d, eps=eps), h)
         .add_vertex("norm_f_s", ScaleVertex(head_scale(cfg)), "norm_f")
         .add_layer("head", RnnOutputLayer(n_out=V, activation="softmax",
                                           loss="sparse_mcxent"), "norm_f_s")
         .set_outputs("head")
         .set_input_types(InputType.recurrent(1, cfg["served_context"])))
    return ComputationGraph(g.build())
