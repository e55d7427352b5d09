"""kanana-2-30b-a3b-ep8: one chip's share of kanana-2-30b-a3b-instruct-2601
(latent attention; a dense lead layer, then sparse layers of a sigmoid
router with a selection bias, shared experts and `n_routed_experts_total`
routed experts) at the widths of the sibling .json, as the training graph:
embedding, the first `num_hidden_layers` blocks, the head and next-token
cross-entropy, the mean over a batch's positions. A sparse layer holds
`n_routed_experts` of the routed experts; its shared experts are one
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

V, H, EPS = CFG["vocab_size"], CFG["hidden_size"], CFG["rms_norm_eps"]
assert CFG["q_lora_rank"] is None and CFG["rope_scaling"] is None
tokens = data_layer(name="tokens", size=V)
labels = data_layer(name="labels", size=V)
h = embedding_layer(input=tokens, size=H, name="embed")
chosen = []
for i in range(CFG["num_hidden_layers"]):
    sparse = CFG["mlp_layer_types"][i] == "sparse"
    with remat_block(f"block{i}"):
        a = multi_head_attention_layer(
            input=rms_norm_layer(h, epsilon=EPS, name=f"l{i}_norm1"),
            num_heads=CFG["num_attention_heads"],
            kv_latent_dim=CFG["kv_lora_rank"], head_dim=CFG["qk_nope_head_dim"],
            rope_head_dim=CFG["qk_rope_head_dim"], value_head_dim=CFG["v_head_dim"],
            rope_theta=CFG["rope_theta"], rope_interleave=CFG["rope_interleave"],
            attention_mask="causal", norm_epsilon=EPS, size=H, name=f"l{i}_attn")
        h = addto_layer([h, a], name=f"l{i}_res1")
        x = rms_norm_layer(h, epsilon=EPS, name=f"l{i}_norm2")
        if sparse:
            m = moe_layer(
                input=x, experts=CFG["n_routed_experts_total"],
                experts_per_token=CFG["num_experts_per_tok"],
                expert_width=CFG["moe_intermediate_size"],
                experts_held=(CFG["experts_held_first"], CFG["n_routed_experts"]),
                norm_topk_prob=CFG["norm_topk_prob"],
                routed_scaling_factor=CFG["routed_scaling_factor"],
                score_function=CFG["scoring_func"], selection_bias=True,
                n_group=CFG["n_group"], name=f"l{i}_moe")
            shared = gated_mlp_layer(
                x, CFG["n_shared_experts"] * CFG["moe_intermediate_size"], name=f"l{i}_shared")
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
