import json
import os

import numpy as np

from perfbench.flops import recurrence_call, roofline_seconds
from perfbench.harness import load_module
from perfbench.tests.helpers import REPO

fl = load_module(os.path.join(REPO, "perfbench/flops/seqtoseq.py"))
ref = load_module(os.path.join(REPO, "perfbench/reference/seqtoseq.py"))
TOY = dict(word_vector_dim=4, encoder_size=3, decoder_size=5,
           source_dict_dim=11, target_dict_dim=7)


def test_forward_flops_against_a_hand_count():
    # one pair, 2 source tokens, 3 target tokens; e=4 h=3 d=5 v=7
    src = 2 * (2 * (2 * 4 * 9 + 2 * 3 * 9) + 2 * 6 * 5)          # 2 tokens
    per_trg = (2 * 5 * 5 + 2 * (2 * 5 + 2 * 6)                    # attention
               + 2 * 6 * 15 + 2 * 4 * 15 + 2 * 5 * 15             # decoder GRU
               + 2 * 5 * 7)                                       # output
    boot = 2 * 3 * 5
    assert fl.forward_flops(TOY, [2], [3]) == src + 3 * per_trg + boot
    lens = {"source_language_word": [2], "target_language_next_word": [3]}
    assert fl.train_step_flops(TOY, lens) == 3 * (src + 3 * per_trg + boot)


def test_flops_against_the_references_parameter_shapes():
    """Per token, every weight matrix is used once: two operations an
    element. Attention over S source positions adds 2 S (d + 2h)."""
    sizes = json.load(open(os.path.join(REPO, "perfbench/configs/seqtoseq-wmt14.json")))
    shapes = ref.param_shapes(sizes)
    n = lambda *names: sum(int(np.prod(shapes[k])) for k in names)
    per_src = 2 * n("enc_fwd_x", "enc_fwd_w", "enc_bwd_x", "enc_bwd_w", "enc_proj")
    per_trg = 2 * n("att_w", "dec_ctx", "dec_word", "dec_w", "out_w")
    s, t = 17, 23
    attention = t * 2 * s * (sizes["decoder_size"] + 2 * sizes["encoder_size"])
    want = s * per_src + t * per_trg + attention + 2 * n("boot")
    assert fl.forward_flops(sizes, [s], [t]) == want
    assert sum(int(np.prod(v)) for v in shapes.values()) == 53455152


def test_recurrence_call_and_roofline():
    peaks = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
    fwd = recurrence_call("gru", 56, 512, 512, 3, 2, False)
    assert fwd["flops"] == 2 * 56 * 512 * 512 * 1536
    assert fwd["bytes"] == 56 * 512 * 1536 * 2 + 512 * 1536 * 2 + 56 * 512 * 512 * 2
    bwd = recurrence_call("gru", 56, 512, 512, 3, 2, True)
    assert bwd["flops"] == 2 * fwd["flops"]
    t, bound = roofline_seconds(fwd, peaks)
    assert bound == "compute" and abs(t - fwd["flops"] / 197e12) < 1e-12
    calls = fl.train_kernel_calls(
        {"encoder_size": 512}, {"source_language_word": (56, 512)})
    assert [c["flops"] for c in calls] == [fwd["flops"]] * 2 + [bwd["flops"]] * 2
