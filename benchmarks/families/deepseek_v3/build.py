"""Drive the program for a DeepSeek-V3-family configuration (``model_type:
deepseek_v3``): the graph through the public ``ComputationGraph`` builder
and the layers ``nn/layers`` has for it (RMS norm, latent attention, gated
MLP, mixture of experts with a shared expert beside it), and the
benchmark's weights put in its place. ``GraphDecodeSpec`` finds the blocks
by the kinds of these layers; the vertex names are this file's own."""
from __future__ import annotations

from typing import Dict

from benchmarks.families.lfm2_moe.build import install  # noqa: F401


def build(cfg: Dict, hp: Dict, role: str):
    """An un-initialised ``ComputationGraph`` in the precision the
    configuration states for ``role``; ``install`` gives it weights."""
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    from deeplearning4j_tpu.nn.graph.vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (EmbeddingSequenceLayer,
                                              GatedMLP, LatentAttentionLayer,
                                              MixtureOfExpertsLayer, RMSNorm,
                                              RnnOutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam

    V, d, eps = cfg["vocab_size"], cfg["hidden_size"], cfg["rms_norm_eps"]
    if cfg["q_lora_rank"] is not None or cfg["rope_scaling"] is not None:
        raise ValueError("a q latent and rotary scaling are not supported")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("grouped expert selection is not supported")
    upd = Adam(hp["learning_rate"], beta1=hp["beta1"], beta2=hp["beta2"],
               epsilon=hp["epsilon"])
    g = (NeuralNetConfiguration(seed=0, updater=upd, weight_init="relu",
                                activation="identity", **{
                                    k: v for k, v in cfg["precision"][role].items()
                                    if v is not None})
         .graph_builder().add_inputs("tokens")
         .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=d), "tokens"))
    h = "embed"
    for i in range(cfg["num_hidden_layers"]):
        b = f"l{i}_"
        g = (g.add_layer(b + "norm1", RMSNorm(n_out=d, eps=eps), h)
             .add_layer(b + "attn", LatentAttentionLayer(
                 n_out=d, n_heads=cfg["num_attention_heads"],
                 qk_nope_dim=cfg["qk_nope_head_dim"],
                 qk_rope_dim=cfg["qk_rope_head_dim"],
                 v_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
                 rope_theta=float(cfg["rope_theta"]), norm_eps=eps),
                 b + "norm1")
             .add_vertex(b + "add1", ElementWiseVertex("add"), h, b + "attn")
             .add_layer(b + "norm2", RMSNorm(n_out=d, eps=eps), b + "add1"))
        if i < cfg["first_k_dense_replace"]:
            g = g.add_layer(b + "ffn",
                            GatedMLP(n_hidden=cfg["intermediate_size"]),
                            b + "norm2")
            parts = [b + "ffn"]
        else:
            # the routed experts and, beside them, the shared expert: ONE
            # gated MLP as wide as n_shared_experts experts, on every token
            g = (g.add_layer(b + "ffn", MixtureOfExpertsLayer(
                     n_experts=cfg["n_routed_experts"],
                     top_k=cfg["num_experts_per_tok"],
                     n_hidden=cfg["moe_intermediate_size"],
                     norm_topk=cfg["norm_topk_prob"], norm_eps=1e-20,
                     routed_scaling_factor=float(
                         cfg["routed_scaling_factor"])), b + "norm2")
                 .add_layer(b + "shared", GatedMLP(
                     n_hidden=cfg["n_shared_experts"]
                     * cfg["moe_intermediate_size"]), b + "norm2"))
            parts = [b + "ffn", b + "shared"]
        g = g.add_vertex(b + "add2", ElementWiseVertex("add"), b + "add1",
                         *parts)
        h = b + "add2"
    g = (g.add_layer("norm_f", RMSNorm(n_out=d, eps=eps), h)
         .add_layer("head", RnnOutputLayer(n_out=V, activation="softmax",
                                           loss="sparse_mcxent"), "norm_f")
         .set_outputs("head")
         .set_input_types(InputType.recurrent(1, cfg["served_context"])))
    return ComputationGraph(g.build())
