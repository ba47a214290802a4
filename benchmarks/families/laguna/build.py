"""Drive the program for a Laguna configuration (``model_type: laguna``):
the graph through the public ``ComputationGraph`` builder and the layers
``nn/layers`` has for it (RMS norm, ``SelfAttentionLayer`` with a head
size of its own, a per-head output gate, partial or YaRN-scaled rotation
and, on the sliding layers, a window; gated MLP; mixture of experts with a
shared expert beside it), and the benchmark's weights put in its place.
The two kinds of attention layer are ONE class with different fields.
``GraphDecodeSpec`` finds the blocks by the kinds of these layers; the
vertex names are this file's own."""
from __future__ import annotations

from typing import Dict

from benchmarks.families.lfm2_moe.build import install  # noqa: F401

NORM_EPS = 1e-20      # in the chosen weights' sum: none to speak of


def attention_fields(cfg: Dict, i: int) -> Dict:
    """The fields of layer ``i``'s ``SelfAttentionLayer`` from the
    configuration's keys (plain values: ``reference.py`` reads the same
    keys its own way and imports nothing from here)."""
    kind = cfg["layer_types"][i]
    rp = cfg["rope_parameters"][kind]
    Dh = cfg["head_dim"]
    rotary = int(round(Dh * float(rp.get("partial_rotary_factor", 1))))
    scaling = None
    if rp["rope_type"] != "default":
        scaling = {k: rp[k] for k in (
            "rope_type", "factor", "original_max_position_embeddings",
            "beta_fast", "beta_slow", "attention_factor") if k in rp}
    if kind not in ("full_attention", "sliding_attention"):
        raise ValueError(f"unknown layer type {kind!r}")
    return dict(
        n_out=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads_per_layer"][i],
        n_kv_heads=cfg["num_key_value_heads"], head_size=Dh, causal=True,
        bias=bool(cfg["attention_bias"]), head_gate=bool(cfg["gating"]),
        rope_theta=float(rp["rope_theta"]),
        rotary_dim=None if rotary == Dh else rotary, rope_scaling=scaling,
        window=cfg["sliding_window"] if kind == "sliding_attention" else None)


def build(cfg: Dict, hp: Dict, role: str):
    """An un-initialised ``ComputationGraph`` in the precision the
    configuration states for ``role``; ``install`` gives it weights."""
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    from deeplearning4j_tpu.nn.graph.vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (EmbeddingSequenceLayer,
                                              GatedMLP,
                                              MixtureOfExpertsLayer, RMSNorm,
                                              RnnOutputLayer,
                                              SelfAttentionLayer)
    from deeplearning4j_tpu.optimize.updaters import Adam

    V, d, eps = cfg["vocab_size"], cfg["hidden_size"], cfg["rms_norm_eps"]
    n = cfg["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(cfg[key]) != n:
            raise ValueError(f"{key} has {len(cfg[key])} entries for {n} layers")
    upd = Adam(hp["learning_rate"], beta1=hp["beta1"], beta2=hp["beta2"],
               epsilon=hp["epsilon"])
    g = (NeuralNetConfiguration(seed=0, updater=upd, weight_init="relu",
                                activation="identity", **{
                                    k: v for k, v in cfg["precision"][role].items()
                                    if v is not None})
         .graph_builder().add_inputs("tokens")
         .add_layer("embed", EmbeddingSequenceLayer(n_in=V, n_out=d), "tokens"))
    h = "embed"
    for i in range(n):
        b = f"l{i}_"
        g = (g.add_layer(b + "norm1", RMSNorm(n_out=d, eps=eps), h)
             .add_layer(b + "attn",
                        SelfAttentionLayer(**attention_fields(cfg, i)),
                        b + "norm1")
             .add_vertex(b + "add1", ElementWiseVertex("add"), h, b + "attn")
             .add_layer(b + "norm2", RMSNorm(n_out=d, eps=eps), b + "add1"))
        if cfg["mlp_layer_types"][i] == "dense":
            g = g.add_layer(b + "ffn",
                            GatedMLP(n_hidden=cfg["intermediate_size"]),
                            b + "norm2")
            parts = [b + "ffn"]
        else:
            # the routed experts and, beside them, the shared expert: one
            # gated MLP on every token, added unweighted
            g = (g.add_layer(b + "ffn", MixtureOfExpertsLayer(
                     n_experts=cfg["num_experts"],
                     top_k=cfg["num_experts_per_tok"],
                     n_hidden=cfg["moe_intermediate_size"],
                     norm_topk=True, norm_eps=NORM_EPS,
                     routed_scaling_factor=float(
                         cfg["moe_routed_scaling_factor"]),
                     score=cfg["assumed"]["router_score"]), b + "norm2")
                 .add_layer(b + "shared", GatedMLP(
                     n_hidden=cfg["shared_expert_intermediate_size"]),
                     b + "norm2"))
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
