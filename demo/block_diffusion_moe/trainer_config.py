"""Block-diffusion training of a small mixture-of-experts transformer.

The recipe the layers of this demo exist for: a sequence of L tokens is fed
as 2L positions, a NOISED copy (tokens of each block replaced by a mask id
with the block's own probability t) followed by the CLEAN copy; attention
runs under the block-diffusion mask (`attention_mask="block_diffusion"`: a
noised position sees its own block's noised tokens and the clean tokens of
earlier blocks, a clean position the clean tokens of its own and earlier
blocks); every block's feed-forward is a sparse-expert layer (`moe_layer`:
a float32 softmax router, the top experts_per_token renormalised, SwiGLU
experts, no token dropped); the loss is the cross-entropy of the noised
half's logits against the clean tokens, each masked position weighted 1/t
(`cross_entropy(weight=...)`, a weight a position). Each transformer block
is one recomputation block (`settings(remat="block")`).

Tiny by default (a CPU smoke, seconds); every size is a config arg:
  paddle train --config=trainer_config.py --config_args=dim=256,layers=4
`experts_held=first:count` makes the layer hold a range of its experts, as
one chip of an expert-parallel deployment does.
"""

from paddle.trainer_config_helpers import *

VOCAB = get_config_arg("vocab", int, 97)          # the last id is the mask id
SEQ_LEN = get_config_arg("seq_len", int, 32)      # L; the model reads 2L
BLOCK = get_config_arg("block_length", int, 4)
DIM = get_config_arg("dim", int, 64)
HEADS = get_config_arg("heads", int, 4)
KV_HEADS = get_config_arg("kv_heads", int, 2)
HEAD_DIM = get_config_arg("head_dim", int, 16)
EXPERTS = get_config_arg("experts", int, 8)
PER_TOKEN = get_config_arg("experts_per_token", int, 2)
WIDTH = get_config_arg("expert_width", int, 32)
LAYERS = get_config_arg("layers", int, 2)
HELD = get_config_arg("experts_held", str, "")
held = tuple(int(x) for x in HELD.split(":")) if HELD else None

define_py_data_sources2(
    train_list="train.list", test_list=None,
    module="dataprovider", obj="process",
    args={"vocab": VOCAB, "seq_len": SEQ_LEN, "block_length": BLOCK},
)

settings(
    batch_size=get_config_arg("batch_size", int, 8),
    learning_rate=1e-3,
    learning_method=AdamOptimizer(),
    dtype=get_config_arg("dtype", str, "float32"),
    remat="block",
)

tokens = data_layer(name="tokens", size=VOCAB)     # x_t ; x_0, 2L ids
labels = data_layer(name="labels", size=VOCAB)     # x_0, L ids
weights = data_layer(name="weights", size=1)       # 1/t where masked, else 0

h = embedding_layer(input=tokens, size=DIM, name="embed")
for i in range(LAYERS):
    with remat_block(f"block{i}"):
        a = multi_head_attention_layer(
            input=rms_norm_layer(h, name=f"l{i}_norm1"),
            num_heads=HEADS, num_kv_heads=KV_HEADS, head_dim=HEAD_DIM,
            qk_norm=True, rope_theta=10000.0,
            attention_mask="block_diffusion", block_length=BLOCK,
            size=DIM, name=f"l{i}_attn")
        h = addto_layer([h, a], name=f"l{i}_res1")
        m = moe_layer(
            input=rms_norm_layer(h, name=f"l{i}_norm2"),
            experts=EXPERTS, experts_per_token=PER_TOKEN, expert_width=WIDTH,
            experts_held=held, name=f"l{i}_moe")
        h = addto_layer([h, m], name=f"l{i}_res2")
h = seq_slice_layer(rms_norm_layer(h, name="final_norm"), parts=2, part=0)
out = fc_layer(input=h, size=VOCAB, act=SoftmaxActivation(), bias_attr=False, name="head")
cross_entropy(input=out, label=labels, weight=weights, name="cost")
