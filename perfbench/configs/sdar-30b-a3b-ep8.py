"""sdar-30b-a3b-ep8: one chip's share of SDAR-30B-A3B-Chat (a Qwen3-MoE
body trained by block diffusion) at the widths of the sibling .json, as the
training graph: embedding, `num_hidden_layers` blocks of grouped-query
attention under the block-diffusion mask and a sparse-expert layer that
holds `num_experts` of the `num_experts_routed` experts, the head and the
weighted cross-entropy over the first L of the 2L positions. Each block is
one recomputation block (`remat="block"`)."""
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
    module="perfbench.provider_typed", obj="process",
    args={"feed": get_config_arg("feed", str, "")})
settings(batch_size=get_config_arg("batch", int, 4),
         learning_rate=S["learning_rate"],
         learning_method=AdamOptimizer(beta1=S["adam_beta1"],
                                       beta2=S["adam_beta2"],
                                       epsilon=S["adam_epsilon"]),
         gradient_clipping_threshold=S["gradient_clipping_threshold"],
         dtype=S["dtype"], remat=S["remat"])

V, H, EPS = CFG["vocab_size"], CFG["hidden_size"], CFG["rms_norm_eps"]
tokens = data_layer(name="tokens", size=V)
labels = data_layer(name="labels", size=V)
weights = data_layer(name="weights", size=1)
h = embedding_layer(input=tokens, size=H, name="embed")
chosen = []
for i in range(CFG["num_hidden_layers"]):
    with remat_block(f"block{i}"):
        a = multi_head_attention_layer(
            input=rms_norm_layer(h, epsilon=EPS, name=f"l{i}_norm1"),
            num_heads=CFG["num_attention_heads"],
            num_kv_heads=CFG["num_key_value_heads"], head_dim=CFG["head_dim"],
            qk_norm=True, norm_epsilon=EPS, rope_theta=CFG["rope_theta"],
            attention_mask="block_diffusion", block_length=CFG["block_length"],
            size=H, name=f"l{i}_attn")
        h = addto_layer([h, a], name=f"l{i}_res1")
        m = moe_layer(
            input=rms_norm_layer(h, epsilon=EPS, name=f"l{i}_norm2"),
            experts=CFG["num_experts_routed"],
            experts_per_token=CFG["num_experts_per_tok"],
            expert_width=CFG["moe_intermediate_size"],
            experts_held=(CFG["experts_held_first"], CFG["num_experts"]),
            norm_topk_prob=CFG["norm_topk_prob"], name=f"l{i}_moe")
        h = addto_layer([h, m], name=f"l{i}_res2")
    chosen.append(get_output_layer(m, "chosen", name=f"l{i}_chosen"))
h = seq_slice_layer(rms_norm_layer(h, epsilon=EPS, name="final_norm"),
                    parts=2, part=0, name="noised_half")
out = fc_layer(input=h, size=V, act=SoftmaxActivation(), bias_attr=False, name="head")
cost = cross_entropy(input=out, label=labels, weight=weights, name="cost")
# the experts each layer chose stay outputs beside the cost (4 MB of ids a
# step, left on the device): the comparison that decides `correct` computes
# the plain reference under the program's own choices (`routing_map`)
outputs(cost, *chosen)
