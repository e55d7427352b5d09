"""seqtoseq-wmt14: the demo's own gru_encoder_decoder, unchanged, at the
widths of the sibling .json, as the training graph with the settings users
of this repo train it with on a TPU."""
import json
import os
import sys

import paddle_tpu

# the sizes: the sibling .json, or the one the harness names (a later
# configuration may reuse this file with its own sizes)
with open(get_config_arg("config_json", str, "")
          or os.path.splitext(os.path.abspath(__file__))[0] + ".json") as _f:
    CFG = json.load(_f)
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(paddle_tpu.__file__))),
    "demo", "seqToseq"))
from seqToseq_net import gru_encoder_decoder  # noqa: E402

S = CFG["settings"]
define_py_data_sources2(
    train_list=get_config_arg("feed_list", str, ""), test_list=None,
    module="perfbench.provider", obj="process",
    args={"feed": get_config_arg("feed", str, "")})
settings(batch_size=get_config_arg("batch", int, 256),
         learning_rate=S["learning_rate"],
         learning_method=AdamOptimizer(beta1=S["adam_beta1"],
                                       beta2=S["adam_beta2"],
                                       epsilon=S["adam_epsilon"]),
         gradient_clipping_threshold=S["gradient_clipping_threshold"],
         dtype=S["dtype"], pallas_rnn=S["pallas_rnn"],
         pallas_decoder=S["pallas_decoder"])
gru_encoder_decoder(source_dict_dim=CFG["source_dict_dim"],
                    target_dict_dim=CFG["target_dict_dim"],
                    is_generating=False,
                    word_vector_dim=CFG["word_vector_dim"],
                    encoder_size=CFG["encoder_size"],
                    decoder_size=CFG["decoder_size"])
