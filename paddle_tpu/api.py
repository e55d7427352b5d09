"""Embedding API — drive the framework from user Python programs.

The role of the reference's SWIG binding
(/root/reference/paddle/api/PaddleAPI.h:92-799 and
paddle/py_paddle/util.py): load a parsed config, build a machine, run
forward/forwardBackward from numpy data, read/write parameters, and run
beam-search generation — without the Trainer CLI. No SWIG here: the
framework is already Python, so this module is a thin numpy-faced wrapper
over GradientMachine/Updater/checkpoint.

Typical prediction flow (mirrors demo/sentiment/predict.py against the
reference):

    conf = parse_config("trainer_config.py", "is_predict=1")
    machine = GradientMachine.createFromConfigProto(conf.model_config)
    machine.loadParameters("./output/pass-00009")
    conv = DataProviderConverter([integer_value_sequence(dict_dim)],
                                 machine.input_layer_names())
    out = machine.forwardTest(conv([[word_ids], [word_ids2]]))
    prob = out[0]["value"]
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from paddle_tpu.data.feeder import BatchAssembler
from paddle_tpu.graph.argument import Argument
from paddle_tpu.graph.machine import GradientMachine as _CoreMachine
from paddle_tpu.proto import ModelConfig, OptimizationConfig
from paddle_tpu.utils.logging import logger

__all__ = [
    "initPaddle",
    "GradientMachine",
    "DataProviderConverter",
    "SequenceGenerator",
]


def initPaddle(*args: str) -> None:
    """Process-level init (ref: swig_paddle.initPaddle). Flags in
    ``--name=value`` form; unknown names are ignored."""
    from paddle_tpu.utils.flags import FLAGS

    FLAGS.parse(list(args))
    from paddle_tpu.utils.device import select_platform

    select_platform(FLAGS.use_tpu)


class DataProviderConverter:
    """samples → feed dict of Arguments (ref: py_paddle
    DataProviderWrapperConverter / dataprovider_converter.py:22-56).

    ``input_types`` are the @provider slot declarations; ``slot_names``
    the data-layer names in config input order.
    """

    def __init__(self, input_types: Sequence, slot_names: Sequence[str]):
        self.assembler = BatchAssembler(input_types, slot_names)

    def __call__(self, samples: List[Sequence[Any]]) -> Dict[str, Argument]:
        return self.assembler.assemble(samples)


class GradientMachine:
    """Numpy-faced machine wrapper (ref: PaddleAPI.h:626 GradientMachine)."""

    def __init__(self, model_config: ModelConfig, params=None, seed: int = 1):
        self._core = _CoreMachine(model_config)
        self.model_config = model_config
        self.params = params if params is not None else self._core.init_params(seed=seed)
        self._fwd_test = None

    # -- construction ----------------------------------------------------

    @classmethod
    def createFromConfigProto(cls, model_config: ModelConfig, seed: int = 1):
        return cls(model_config, seed=seed)

    @classmethod
    def createFromConfigFile(cls, config_file: str, config_args: str = ""):
        from paddle_tpu.config import parse_config

        conf = parse_config(config_file, config_args)
        return cls(conf.model_config)

    # -- parameters ------------------------------------------------------

    def loadParameters(self, path: str) -> None:
        """Load parameters from a checkpoint dir (pass-NNNNN), a save_dir
        containing pass dirs (latest wins), or a merged-model .npz."""
        import jax.numpy as jnp

        from paddle_tpu.trainer import checkpoint as ckpt

        if os.path.isfile(path):  # merged model (cli merge_model output)
            with np.load(path, allow_pickle=False) as z:
                loaded = {
                    k: jnp.asarray(z[k]) for k in z.files if k != "__config_json__"
                }
            for name in self.params:
                assert name in loaded, f"parameter {name!r} missing from {path}"
            self.params = {k: loaded[k] for k in self.params}
        else:
            if not ckpt.has_params_tree(path):
                latest = ckpt.latest_pass(path)
                assert latest is not None, f"no checkpoint under {path}"
                path = os.path.join(path, ckpt.PASS_FMT % latest)
            # fallback=False: an inference embedding asked for THIS
            # checkpoint — never quarantine it or silently substitute an
            # older pass (verification still fails loudly on corruption)
            self.params, _, _ = ckpt.load_checkpoint(
                path, None, expected_params=self.params, fallback=False
            )
        self._fwd_test = None

    def saveParameters(self, save_dir: str, pass_id: int = 0) -> None:
        from paddle_tpu.trainer import checkpoint as ckpt

        ckpt.save_checkpoint(save_dir, pass_id, self.params)

    def getParameterNames(self) -> List[str]:
        return sorted(self.params.keys())

    def getParameter(self, name: str) -> np.ndarray:
        return np.asarray(self.params[name])

    def setParameter(self, name: str, value) -> None:
        import jax.numpy as jnp

        cur = self.params[name]
        arr = jnp.asarray(value, dtype=cur.dtype)
        assert arr.shape == cur.shape, f"{name}: {arr.shape} != {cur.shape}"
        self.params[name] = arr
        self._fwd_test = None

    # -- inference -------------------------------------------------------

    def input_layer_names(self) -> List[str]:
        return list(self.model_config.input_layer_names)

    def output_layer_names(self) -> List[str]:
        return list(self._core.network.output_layer_names)

    def _feed(self, in_args) -> Dict[str, Argument]:
        """Normalize a feed: dict keyed by data-layer names, a positionally
        keyed dict ("0", "1", ... from DataProviderWrapperConverter), or a
        list of Arguments in config input order."""
        names = self.input_layer_names()
        if isinstance(in_args, dict):
            if any(n in in_args for n in names):
                return in_args
            # positional string keys → input order
            return {n: in_args[str(i)] for i, n in enumerate(names) if str(i) in in_args}
        return {n: a for n, a in zip(names, in_args)}

    def forwardTest(self, in_args) -> List[Dict[str, np.ndarray]]:
        """Forward in test mode; one dict per output layer with numpy
        ``value`` / ``id`` / ``sequence_lengths`` entries (the shape of the
        reference's Arguments-out-to-numpy conversion, util.py:136)."""
        in_args = self._feed(in_args)
        if self._fwd_test is None:
            core = self._core

            def fwd(params, args):
                outputs, _ = core.forward(params, args, pass_type="test", rng=None)
                return outputs

            self._fwd_test = jax.jit(fwd)
        outputs = self._fwd_test(self.params, in_args)
        result = []
        for name in self.output_layer_names():
            arg = outputs[name]
            entry: Dict[str, np.ndarray] = {}
            if arg.value is not None:
                entry["value"] = np.asarray(arg.value)
            if arg.ids is not None:
                entry["id"] = np.asarray(arg.ids)
            if arg.seq_lengths is not None:
                entry["sequence_lengths"] = np.asarray(arg.seq_lengths)
            result.append(entry)
        return result

    def forwardBackward(self, in_args: Dict[str, Argument], rng=None):
        """One loss+gradient evaluation (custom training loops, ref:
        PaddleAPI.h GradientMachine::forwardBackward). Returns
        (loss: float, grads: dict name→numpy)."""
        grad_fn = self._core.grad_fn()
        loss, grads, _, _ = grad_fn(self.params, in_args, rng)
        # row-sparse embedding grads densify for this numpy API (small
        # models only; training never materializes them)
        dense = {
            k: np.asarray(v.to_dense() if hasattr(v, "to_dense") else v)
            for k, v in grads.items()
        }
        return float(loss), dense

    # -- generation ------------------------------------------------------

    def asSequenceGenerator(
        self,
        dict_file: str = "",
        begin_token: Optional[int] = None,
        end_token: Optional[int] = None,
        max_length: Optional[int] = None,
        beam_size: Optional[int] = None,
    ) -> "SequenceGenerator":
        """Overrides (when given) are written into the generator sub-model
        config before the generation graph is traced — same knobs the
        reference SWIG API exposes (PaddleAPI.h:775)."""
        return SequenceGenerator(
            self, dict_file,
            begin_token=begin_token, end_token=end_token,
            max_length=max_length, beam_size=beam_size,
        )

    def asDecodeEngine(self, slots: int = 8, prompt_tokens: int = 32,
                       queue_cap: int = 0, request_timeout_s: float = 60.0,
                       decode_block=1, registry=None,
                       pipeline: bool = True, fused_step: bool = False,
                       spec_tokens="0", slot_dtype: str = "f32"):
        """The continuous-batching engine over this machine's generator
        graph (doc/serving.md) — the concurrent-use superset of
        :class:`SequenceGenerator`: submit() from any thread, slot-based
        greedy decode (beam_size=1 semantics, token-for-token equal to
        ``generate`` at beam 1), admission/eviction per iteration.
        Returns an UNstarted :class:`paddle_tpu.serving.Engine`; call
        ``.start()`` (pays the compiles) and ``.drain()`` when done."""
        from paddle_tpu.serving.frontend import build_engine

        return build_engine(
            self._core, self.params, slots=slots,
            prompt_tokens=prompt_tokens, queue_cap=queue_cap,
            request_timeout_s=request_timeout_s, decode_block=decode_block,
            registry=registry, pipeline=pipeline, fused_step=fused_step,
            spec_tokens=spec_tokens, slot_dtype=slot_dtype,
        )


def _feed_signature(in_args):
    """Best-effort batch-shape signature of a feed — what jit retraces
    on. Unhashable/unreadable feeds collapse to one bucket (only the
    first call is then flagged cold_start, the pre-signature behavior)."""
    try:
        parts = []
        items = (sorted(in_args.items()) if isinstance(in_args, dict)
                 else enumerate(in_args))
        for name, arg in items:
            for field in ("ids", "value", "seq_lengths"):
                v = getattr(arg, field, None)
                if v is not None:
                    parts.append((str(name), field, tuple(np.asarray(v).shape)))
        return tuple(parts)
    except Exception:
        return None


def _feed_batch_size(in_args) -> int:
    """Sample count of a feed, from any input's leading dimension —
    best-effort, at least 1 (one error record beats none)."""
    try:
        for arg in (in_args.values() if isinstance(in_args, dict) else in_args):
            for field in ("seq_lengths", "ids", "value"):
                v = getattr(arg, field, None)
                if v is not None:
                    return max(int(np.asarray(v).shape[0]), 1)
    except Exception:
        pass
    return 1


def _prompt_token_counts(in_args) -> List[int]:
    """Per-sample prompt token counts from a feed's first sequence input
    (its seq_lengths column); best-effort — a dense-only feed yields
    an empty list and the request records fall back to 0."""
    try:
        for arg in (in_args.values() if isinstance(in_args, dict) else in_args):
            sl = getattr(arg, "seq_lengths", None)
            if sl is not None:
                return [int(x) for x in np.asarray(sl).reshape(-1)]
    except Exception:
        pass
    return []


class SequenceGenerator:
    """Beam-search generation façade (ref: PaddleAPI.h:775 and
    ISequenceResults). Works on configs whose sub-model declares a
    generator (beam_search in the DSL).

    One call = one static run-to-completion cohort. For CONCURRENT use
    — many callers, mixed lengths, latency targets — the continuous-
    batching engine subsumes this API at beam_size=1:
    ``machine.asDecodeEngine(...).start()`` then ``submit()`` per
    request (doc/serving.md; greedy outputs are token-for-token equal,
    pinned by tests/test_engine.py). This class keeps its PR-8
    one-cohort request-record contract unchanged."""

    def __init__(
        self,
        machine: GradientMachine,
        dict_file: str = "",
        begin_token: Optional[int] = None,
        end_token: Optional[int] = None,
        max_length: Optional[int] = None,
        beam_size: Optional[int] = None,
    ):
        self.machine = machine
        self.words: Optional[List[str]] = None
        if dict_file:
            with open(dict_file) as f:
                self.words = [line.rstrip("\n") for line in f]
        # apply overrides to a private copy of the model config so they
        # never leak into the machine (or later generators); a dedicated
        # core machine traces from the copy, sharing the live params
        import copy

        model_cfg = machine.model_config
        if any(x is not None for x in (begin_token, end_token, max_length, beam_size)):
            model_cfg = copy.deepcopy(machine.model_config)
        subs = [s for s in model_cfg.sub_models if s.generator is not None]
        assert subs, "config declares no generator sub-model (beam_search)"
        self.sub = subs[0]
        group_cfg = next(
            (l for l in model_cfg.layers if l.name == self.sub.name), None
        )
        if max_length is not None:
            self.sub.generator.max_num_frames = int(max_length)
        if beam_size is not None and group_cfg is not None:
            group_cfg.beam_size = int(beam_size)
            self.sub.generator.beam_size = int(beam_size)
        if begin_token is not None and group_cfg is not None:
            group_cfg.bos_id = int(begin_token)
        if end_token is not None and group_cfg is not None:
            group_cfg.eos_id = int(end_token)
        self._core = (
            machine._core if model_cfg is machine.model_config else _CoreMachine(model_cfg)
        )
        self._fwd = None
        self._seen_sigs: set = set()

    def generate(self, in_args: Dict[str, Argument]) -> List[List[Dict[str, Any]]]:
        """Returns, per input sample, a list of beams:
        ``{"ids": [...], "score": float, "words": [...]}`` sorted best-first.

        When telemetry is configured (``observability.metrics.configure``),
        every call emits one ``kind=request`` record per input sample —
        the call is one batch cohort, each sample a zero-queue-wait
        request (doc/observability.md "Serving telemetry") — so even
        embedding-API generation carries request-level latency evidence."""
        import time as _time

        from paddle_tpu.observability import metrics as _metrics
        from paddle_tpu.observability import serving as _serving

        # all instrumentation bookkeeping (feed signature, prompt lens)
        # is gated like log_oneshot itself: the telemetry-off hot path
        # pays nothing
        telemetry = _metrics.enabled()
        # cold_start marks any call that pays a jit trace+compile: the
        # first one, AND any new batch-shape signature (jit retraces per
        # shape) — steady-state latency aggregations must be able to
        # split both out
        sig = _feed_signature(in_args) if telemetry else None
        cold_start = telemetry and (
            self._fwd is None or sig not in self._seen_sigs
        )
        if self._fwd is None:
            core = self._core

            def fwd(params, args):
                outputs, _ = core.forward(params, args, pass_type="gen", rng=None)
                return outputs

            self._fwd = jax.jit(fwd)
        prompt_lens = _prompt_token_counts(in_args) if telemetry else []
        t0 = _time.perf_counter()
        try:
            outputs = jax.block_until_ready(
                self._fwd(self.machine.params, in_args)
            )
        except Exception:
            if telemetry:
                # even a dense-only feed (no seq_lengths → empty
                # prompt_lens) must leave error evidence: size the
                # cohort from the feed
                _serving.log_oneshot(
                    prompt_lens, [], _time.perf_counter() - t0,
                    beam_size=self.sub.generator.beam_size,
                    outcome="error",
                    n=len(prompt_lens) or _feed_batch_size(in_args),
                    cold_start=cold_start,
                )
            raise
        service_s = _time.perf_counter() - t0
        if telemetry:
            # only a SUCCESSFUL forward warms the signature: a failed
            # trace/compile isn't cached by jit, so the retry pays the
            # compile again and must be flagged cold_start again
            self._seen_sigs.add(sig)
        group = self.sub.name
        best = outputs[group]
        beams = outputs.get(f"{group}@beams")
        if beams is not None:
            beam_ids = np.asarray(beams.ids)               # [B, K, T]
            scores = np.asarray(beams.value)               # [B, K]
            lens = np.asarray(beams.sub_seq_lengths)       # [B, K]
        else:
            beam_ids = np.asarray(best.ids)[:, None]       # [B, 1, T]
            scores = np.zeros(beam_ids.shape[:2], np.float32)
            lens = np.asarray(best.seq_lengths)[:, None]
        results = []
        for b in range(beam_ids.shape[0]):
            sample = []
            for k in range(beam_ids.shape[1]):
                ids = [int(i) for i in beam_ids[b, k, : lens[b, k]]]
                entry: Dict[str, Any] = {"ids": ids, "score": float(scores[b, k])}
                if self.words is not None:
                    entry["words"] = [
                        self.words[i] if 0 <= i < len(self.words) else "<unk>"
                        for i in ids
                    ]
                sample.append(entry)
            sample.sort(key=lambda e: -e["score"])
            results.append(sample)
        # gen_tokens counts the BEST beam's tokens — taken from the
        # sorted results the caller receives, not raw beam slot 0 (the
        # forward may return beams in non-score order)
        _serving.log_oneshot(
            prompt_lens if len(prompt_lens) == len(results)
            else [0] * len(results),
            [len(sample[0]["ids"]) if sample else 0 for sample in results],
            service_s, beam_size=self.sub.generator.beam_size,
            cold_start=cold_start,
        )
        return results
