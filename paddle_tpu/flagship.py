"""Flagship bench/dryrun model builders, shared by bench.py and
__graft_entry__.py so neither entry point depends on the other
(reference role: the benchmark configs under demo/ driven by
paddle/trainer/Trainer.cpp's train path).
"""

from __future__ import annotations

import os

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def flagship_config(dict_dim=1000, emb_dim=64, hidden=64, classes=2, mesh_shape=""):
    """Stacked-LSTM text classifier (the sentiment-demo shape) built via the
    DSL."""
    from paddle_tpu.config.builder import fresh_context
    from paddle_tpu.trainer_config_helpers import (
        AdamOptimizer,
        MaxPooling,
        ParamAttr,
        SoftmaxActivation,
        classification_cost,
        data_layer,
        embedding_layer,
        fc_layer,
        outputs,
        pooling_layer,
        settings,
        simple_lstm,
    )

    with fresh_context() as ctx:
        settings(
            batch_size=32,
            learning_rate=1e-3,
            learning_method=AdamOptimizer(),
            mesh_shape=mesh_shape or None,
        )
        words = data_layer(name="words", size=dict_dim)
        emb = embedding_layer(input=words, size=emb_dim, param_attr=ParamAttr(name="emb"))
        lstm = simple_lstm(input=emb, size=hidden)
        pool = pooling_layer(input=lstm, pooling_type=MaxPooling())
        output = fc_layer(input=pool, size=classes, act=SoftmaxActivation(), name="output")
        label = data_layer(name="label", size=classes)
        outputs(classification_cost(input=output, label=label))
        return ctx.finalize()


def example_batch(dict_dim=1000, B=8, T=32, classes=2, seed=0):
    from paddle_tpu.graph import make_ids, make_seq

    rng = np.random.RandomState(seed)
    ids = rng.randint(0, dict_dim, (B, T)).astype(np.int32)
    lengths = rng.randint(T // 2, T + 1, (B,)).astype(np.int32)
    labels = rng.randint(0, classes, (B,)).astype(np.int32)
    return {
        "words": make_seq(None, lengths, ids=ids),
        "label": make_ids(labels),
    }


def nmt_config(vocab=30000, dim=512, dtype="float32", batch_size=64,
               is_generating=False, **gen_kwargs):
    """seqToseq NMT attention encoder-decoder, the BASELINE.md north-star
    workload #2 — the same model the demo config builds (reference
    demo/seqToseq/seqToseq_net.py:65-181). is_generating=True builds the
    beam-search generation graph (gen.conf path); gen_kwargs (beam_size,
    max_length, ...) pass through to gru_encoder_decoder."""
    import importlib.util

    from paddle_tpu.config.builder import fresh_context
    from paddle_tpu.trainer_config_helpers import AdamOptimizer, settings

    from paddle_tpu.config.config_parser import _ensure_compat_path

    _ensure_compat_path()  # the demo imports `paddle.trainer_config_helpers`
    spec = importlib.util.spec_from_file_location(
        "seqToseq_net_bench", os.path.join(REPO, "demo", "seqToseq", "seqToseq_net.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with fresh_context() as ctx:
        settings(
            batch_size=batch_size,
            learning_rate=1e-3,
            learning_method=AdamOptimizer(),
            dtype=dtype,
        )
        mod.gru_encoder_decoder(
            source_dict_dim=vocab,
            target_dict_dim=vocab,
            is_generating=is_generating,
            word_vector_dim=dim,
            encoder_size=dim,
            decoder_size=dim,
            **gen_kwargs,
        )
        return ctx.finalize()


def nmt_gen_config(vocab=30000, dim=512, beam_size=3, max_length=32,
                   dtype="float32", batch_size=64):
    """The seqToseq generation graph at bench shapes (see nmt_config)."""
    return nmt_config(vocab=vocab, dim=dim, dtype=dtype,
                      batch_size=batch_size, is_generating=True,
                      beam_size=beam_size, max_length=max_length)


def nmt_gen_batch(vocab=30000, B=8, T=32, seed=0):
    """Source-only batch for the generation graph."""
    from paddle_tpu.graph import make_seq

    rng = np.random.RandomState(seed)
    ids = rng.randint(2, vocab, (B, T)).astype(np.int32)
    lengths = rng.randint(max(T // 2, 1), T + 1, (B,)).astype(np.int32)
    return {"source_language_word": make_seq(None, lengths, ids=ids)}


def nmt_batch(vocab=30000, B=8, T=32, seed=0):
    from paddle_tpu.graph import make_seq

    rng = np.random.RandomState(seed)

    def seq():
        ids = rng.randint(2, vocab, (B, T)).astype(np.int32)
        lengths = rng.randint(max(T // 2, 1), T + 1, (B,)).astype(np.int32)
        return ids, lengths

    src, src_len = seq()
    trg, trg_len = seq()
    nxt = np.roll(trg, -1, axis=1)
    return {
        "source_language_word": make_seq(None, src_len, ids=src),
        "target_language_word": make_seq(None, trg_len, ids=trg),
        "target_language_next_word": make_seq(None, trg_len, ids=nxt),
    }


def resnet_config(layer_num=50, img_size=224, classes=1000):
    from paddle_tpu.config import parse_config_at

    return parse_config_at(
        os.path.join(REPO, "demo", "model_zoo", "resnet", "resnet.py"),
        f"layer_num={layer_num},img_size={img_size},num_classes={classes}",
    )


def make_image_batch(B, img_size, classes, seed=0):
    from paddle_tpu.graph import make_dense, make_ids

    rng = np.random.RandomState(seed)
    return {
        "input": make_dense(rng.randn(B, 3 * img_size * img_size).astype(np.float32)),
        "label": make_ids(rng.randint(0, classes, (B,)).astype(np.int32)),
    }
