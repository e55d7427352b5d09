"""laguna-xs.2-ep16: one chip's share of Laguna-XS.2 (full and sliding-window
attention mixed, a head count a layer, a per-head output gate, a dense lead
layer, then sparse layers of one shared and `num_experts_routed` routed
experts) at the widths of the sibling .json, as the training graph:
embedding, the first `num_hidden_layers` blocks as `layer_types`,
`mlp_layer_types` and `num_attention_heads_per_layer` name them, the head and
next-token cross-entropy, the mean over a batch's positions. A sparse layer
holds `num_experts` of the routed experts; its shared expert is a
`gated_mlp_layer` beside it. Each block is one recomputation block
(`remat="block"`)."""
import json
import os

# the sizes: the sibling .json, or the one the harness names (a test reuses
# this file with small sizes)
with open(get_config_arg("config_json", str, "")
          or os.path.splitext(os.path.abspath(__file__))[0] + ".json") as _f:
    CFG = json.load(_f)

S = CFG["settings"]
define_py_data_sources2(
    train_list=get_config_arg("feed_list", str, ""), test_list=None,
    module="perfbench.provider", obj="process",
    args={"feed": get_config_arg("feed", str, "")})
settings(batch_size=get_config_arg("batch", int, 4),
         learning_rate=S["learning_rate"],
         learning_method=AdamOptimizer(beta1=S["adam_beta1"],
                                       beta2=S["adam_beta2"],
                                       epsilon=S["adam_epsilon"]),
         gradient_clipping_threshold=S["gradient_clipping_threshold"],
         dtype=S["dtype"], remat=S["remat"])

V, H, EPS, HD = CFG["vocab_size"], CFG["hidden_size"], CFG["rms_norm_eps"], CFG["head_dim"]
tokens = data_layer(name="tokens", size=V)
labels = data_layer(name="labels", size=V)
h = embedding_layer(input=tokens, size=H, name="embed")
chosen = []
for i in range(CFG["num_hidden_layers"]):
    kind = CFG["layer_types"][i]
    rope = CFG["rope_parameters"][kind]
    yarn = rope.get("rope_type", "default") == "yarn"
    sparse = CFG["mlp_layer_types"][i] == "sparse"
    with remat_block(f"block{i}"):
        a = multi_head_attention_layer(
            input=rms_norm_layer(h, epsilon=EPS, name=f"l{i}_norm1"),
            num_heads=CFG["num_attention_heads_per_layer"][i],
            num_kv_heads=CFG["num_key_value_heads"], head_dim=HD,
            rope_theta=rope["rope_theta"],
            rotary_dim=int(round(rope.get("partial_rotary_factor", 1) * HD)),
            rope_yarn=(rope["factor"], rope["original_max_position_embeddings"],
                       rope["beta_fast"], rope["beta_slow"]) if yarn else None,
            rope_attention_factor=rope.get("attention_factor", 1.0),
            attention_mask="sliding_window" if kind == "sliding_attention" else "causal",
            window=CFG["sliding_window"] if kind == "sliding_attention" else 0,
            output_gate=bool(CFG["gating"]), norm_epsilon=EPS,
            size=H, name=f"l{i}_attn")
        h = addto_layer([h, a], name=f"l{i}_res1")
        x = rms_norm_layer(h, epsilon=EPS, name=f"l{i}_norm2")
        if sparse:
            m = moe_layer(
                input=x, experts=CFG["num_experts_routed"],
                experts_per_token=CFG["num_experts_per_tok"],
                expert_width=CFG["moe_intermediate_size"],
                experts_held=(CFG["experts_held_first"], CFG["num_experts"]),
                norm_topk_prob=True,
                routed_scaling_factor=CFG["moe_routed_scaling_factor"], name=f"l{i}_moe")
            shared = gated_mlp_layer(x, CFG["shared_expert_intermediate_size"],
                                     name=f"l{i}_shared")
            h = addto_layer([h, shared, m], name=f"l{i}_res2")
        else:
            h = addto_layer([h, gated_mlp_layer(x, CFG["intermediate_size"], name=f"l{i}_mlp")],
                            name=f"l{i}_res2")
    if sparse:
        chosen.append(get_output_layer(m, "chosen", name=f"l{i}_chosen"))
h = rms_norm_layer(h, epsilon=EPS, name="final_norm")
out = fc_layer(input=h, size=V, act=SoftmaxActivation(), bias_attr=False, name="head")
# the mean over a batch's positions: the trainer takes the mean over sequences
# of each sequence's sum
cost = cross_entropy(input=out, label=labels, coeff=1.0 / CFG["trained_positions"], name="cost")
# the experts each sparse layer chose stay outputs beside the cost: the
# comparison that decides `correct` computes the plain reference under the
# program's own choices (`routing_map`)
outputs(cost, *chosen)
