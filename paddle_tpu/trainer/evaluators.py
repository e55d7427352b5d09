"""Evaluator runtime.

Reference: /root/reference/paddle/gserver/evaluators/Evaluator.cpp
(ClassificationErrorEvaluator:41, SumEvaluator:151, ColumnSumEvaluator:243,
AucEvaluator Evaluator.h:155, PrecisionRecallEvaluator:234, printers
:870-1235), ChunkEvaluator.cpp, CTCErrorEvaluator.cpp.

Evaluators accumulate over batches on the host, in float64. Where an
evaluator's statistic is a masked reduction of its input layers
(`MaskedReductionEvaluator`: classification_error, seq_classification_error,
sum, last-column-sum) one batch's contribution is `batch_state(args)`, a
traceable function of the layers' padded `Argument`s: the trainer calls it
inside the jitted train step, so the step returns a few numbers and the
evaluator's input layers are no program outputs; `eval_batch` is the same
function, jitted, on whatever arrays it is handed (`Trainer.test()`). Every
other evaluator reads its layers' values back and computes in numpy.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.graph.argument import Argument
from paddle_tpu.observability import metrics as obs
from paddle_tpu.proto import EvaluatorConfig, ModelConfig
from paddle_tpu.utils.registry import Registry
from paddle_tpu.utils.stats import stat_timer

evaluator_registry: Registry[type] = Registry("evaluator")


def register_evaluator(*names):
    return evaluator_registry.register(*names)


class Evaluator:
    def __init__(self, cfg: EvaluatorConfig):
        self.cfg = cfg
        self.start()

    def start(self) -> None:
        raise NotImplementedError

    def eval_batch(self, args: List[Argument]) -> None:
        raise NotImplementedError

    def batch_state(self, args: List[Argument]) -> Optional[jax.Array]:
        """One batch's contribution to ``merge_state()`` as a traceable
        function of the input layers' Arguments, or None where the
        evaluator (or these shapes) has no such form and must be fed the
        layers' values on the host. Decided from the evaluator's type and
        the shapes alone, so inside a trace the choice is static."""
        return None

    def result(self) -> Dict[str, float]:
        raise NotImplementedError

    def summary(self) -> str:
        return " ".join(f"{k}={v:.6g}" for k, v in self.result().items())

    # -- distributed merging (the reference's Evaluator::getState /
    # mergeState split, Evaluator.h:81-82: trainers ship a SMALL
    # accumulated state once per period instead of raw activations
    # per batch)

    def merge_state(self) -> Optional[np.ndarray]:
        """Flat float64 vector of accumulated state that merges across
        processes by SUMMATION, or None if this evaluator cannot merge
        that way (raw-record evaluators, printers) — those eval gathered
        full outputs per batch instead."""
        return None

    def load_state(self, vec: np.ndarray) -> None:
        """Inverse of merge_state: replace accumulators with vec."""
        raise NotImplementedError(type(self).__name__)

    # -- helpers

    @staticmethod
    def _rows(arg: Argument) -> np.ndarray:
        """Flatten an output to valid rows [N, D] (masking padding)."""
        # `eval/readback`: np.asarray of a device array IS the
        # device-to-host copy (a whole [B, T, V] output for a softmax
        # layer); what follows it in the evaluator is host arithmetic
        lens = (arg.sub_seq_lengths if arg.sub_seq_lengths is not None
                else arg.seq_lengths)
        with stat_timer("eval/readback"):
            if arg.value is not None:
                v = np.asarray(arg.value)
            else:
                ids = np.asarray(arg.ids)
                v = ids.reshape(ids.shape + (1,)).astype(np.float32)
            if lens is not None:
                lens = np.asarray(lens)
        if arg.sub_seq_lengths is not None:
            rows = [
                v[b, s, :t]
                for b in range(v.shape[0])
                for s, t in enumerate(lens[b])
                if t > 0
            ]
            return np.concatenate(rows, axis=0) if rows else v.reshape(0, v.shape[-1])
        if arg.seq_lengths is not None:
            rows = [v[b, : lens[b]] for b in range(v.shape[0])]
            return np.concatenate(rows, axis=0) if rows else v.reshape(0, v.shape[-1])
        return v

    @staticmethod
    def _label_rows(arg: Argument) -> np.ndarray:
        if arg.ids is not None:
            with stat_timer("eval/readback"):
                ids = np.asarray(arg.ids)
                by_seq = arg.seq_lengths is not None and ids.ndim >= 2
                if by_seq:
                    lens = np.asarray(arg.seq_lengths)
            if by_seq:
                return np.concatenate([ids[b, : lens[b]].reshape(-1) for b in range(ids.shape[0])])
            return ids.reshape(-1)
        return np.argmax(Evaluator._rows(arg), axis=-1)


# ---- evaluators whose per-batch statistic is a masked reduction --------


def _row_layout(arg: Argument) -> Optional[Tuple[int, ...]]:
    """Shape of the axes that index an Argument's rows in the padded form
    ([B]; [B, T] under ``seq_lengths``; [B, S, T] under
    ``sub_seq_lengths``), or None where the lengths do not go with the
    array's rank and the rows have to be gathered on the host."""
    rows = arg.value.shape[:-1] if arg.value is not None else arg.ids.shape
    rank = (3 if arg.sub_seq_lengths is not None
            else 2 if arg.seq_lengths is not None else 1)
    return tuple(rows) if len(rows) == rank else None


def _row_mask(arg: Argument) -> Optional[jax.Array]:
    """True on an Argument's real rows; None where every row is real."""
    if arg.sub_seq_lengths is not None:
        return arg.sub_seq_mask(bool)
    if arg.seq_lengths is not None:
        return arg.seq_mask(bool)
    return None


def _values(arg: Argument) -> jax.Array:
    """[..., D] values in the layer's own dtype; ids read as one column."""
    if arg.value is not None:
        return arg.value
    return arg.ids[..., None].astype(jnp.float32)


def _over_real_rows(x: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    """f32[2]: the sum of ``x`` (one entry a row) over the real rows, and
    how many rows are real. Padding is masked, never gathered."""
    if mask is None:
        n = x.size
    else:
        x, n = jnp.where(mask, x, jnp.zeros_like(x)), mask.sum()
    total = x.sum(dtype=jnp.int32 if x.dtype == bool else jnp.float32)
    return jnp.stack([total.astype(jnp.float32),
                      jnp.asarray(n, jnp.float32)])


@jax.jit
def _classification_state(out: Argument, label: Argument, threshold):
    v = _values(out)
    if v.shape[-1] == 1:
        # one column is the probability of class 1, cut at the threshold
        pred = ((threshold > 0) & (v[..., 0] > threshold)).astype(jnp.int32)
    else:
        # over the values the host would read, in the layer's own dtype;
        # first index on ties, as np.argmax
        pred = jnp.argmax(v, axis=-1)
    labels = (label.ids if label.ids is not None
              else jnp.argmax(label.value, axis=-1))
    return _over_real_rows(pred != labels, _row_mask(out))


@jax.jit
def _seq_classification_state(out: Argument, label: Argument):
    pred = jnp.argmax(out.value, axis=-1)            # [B, T] or [B, S, T]
    ids = label.ids                                  # one a sample, or a frame
    wrong = pred != ids.reshape(ids.shape + (1,) * (pred.ndim - ids.ndim))
    mask = _row_mask(out)
    if mask is not None:
        wrong &= mask
    return _over_real_rows(wrong.any(axis=tuple(range(1, pred.ndim))), None)


@jax.jit
def _sum_state(arg: Argument):
    return _over_real_rows(
        _values(arg).sum(axis=-1, dtype=jnp.float32), _row_mask(arg))


@jax.jit
def _column_sum_state(arg: Argument):
    return _over_real_rows(
        _values(arg)[..., -1].astype(jnp.float32), _row_mask(arg))


class MaskedReductionEvaluator(Evaluator):
    """An evaluator whose accumulated state is a short vector that merges
    by summation and whose per-batch contribution is a masked reduction
    of its input layers: ``batch_state(args)`` is the one definition of
    the statistic, inside the train step and outside it."""

    WIDTH = 2

    def start(self):
        self.state = np.zeros(self.WIDTH, np.float64)

    def add_state(self, state) -> None:
        """Add per-batch states: f32[WIDTH], or stacked [..., WIDTH] (a
        launch of several batches, one a replica), summed in float64."""
        self.state += np.asarray(state, np.float64).reshape(
            -1, self.WIDTH).sum(axis=0)

    def eval_batch(self, args):
        state = self.batch_state(args)
        if state is None:
            state = self.batch_state(self._as_one_sequence(args))
        self.add_state(state)

    def merge_state(self):
        return self.state.copy()

    def load_state(self, vec):
        self.state = np.array(vec, np.float64)

    def _mean(self) -> float:
        """state[0] over the rows counted in state[1]."""
        return float(self.state[0] / max(self.state[1], 1.0))

    def _as_one_sequence(self, args: List[Argument]) -> List[Argument]:
        """Host re-layout of inputs that are not in one padded form (an
        output and a label whose layouts differ): every input's real rows
        gathered, paired by position and cut to the shorter, then laid
        out as ONE sequence padded to a power of two, so that the masked
        form applies and jit sees few shapes."""
        values = self._rows(args[0])
        # a label where the evaluator has one; a weight input is not read
        labels = [self._label_rows(a) for a in args[1:2]]
        n = min(len(r) for r in [values] + labels)
        padded = max(1, 1 << (n - 1).bit_length())
        lens = np.array([n], np.int32)

        def lay(r, shape):
            r = r[:n].reshape(shape)
            pad = np.zeros((padded - n,) + r.shape[1:], r.dtype)
            return np.concatenate([r, pad])[None]

        return [Argument(value=lay(values, (n, -1)), seq_lengths=lens)] + [
            Argument(ids=lay(r, (n,)), seq_lengths=lens) for r in labels]


@register_evaluator("classification_error")
class ClassificationErrorEvaluator(MaskedReductionEvaluator):
    """state: [wrong rows, rows]."""

    def batch_state(self, args):
        out, label = args[0], args[1]
        if _row_layout(out) is None or _row_layout(out) != _row_layout(label):
            return None
        return _classification_state(
            out, label, self.cfg.classification_threshold)

    def result(self):
        return {"classification_error": self._mean()}


@register_evaluator("sum")
class SumEvaluator(MaskedReductionEvaluator):
    """state: [sum of every value, rows]."""

    def batch_state(self, args):
        return None if _row_layout(args[0]) is None else _sum_state(args[0])

    def result(self):
        return {"sum": float(self.state[0]), "mean": self._mean()}


@register_evaluator("last-column-sum")
class ColumnSumEvaluator(MaskedReductionEvaluator):
    """state: [sum of the last column, rows]."""

    def batch_state(self, args):
        return (None if _row_layout(args[0]) is None
                else _column_sum_state(args[0]))

    def result(self):
        return {"column_sum": float(self.state[0]),
                "column_mean": self._mean()}


@register_evaluator("seq_classification_error")
class SequenceClassificationErrorEvaluator(MaskedReductionEvaluator):
    """Per-sequence error (ref: SequenceClassificationErrorEvaluator,
    Evaluator.cpp:111): a sequence counts as wrong if ANY valid frame's
    argmax disagrees with the label. state: [wrong sequences, sequences]."""

    def batch_state(self, args):
        out, label = args[0], args[1]
        if out.value is None:
            frames = None
        elif out.seq_lengths is None:      # no lengths: every frame is real
            frames = tuple(out.value.shape[:-1])
        else:
            frames = _row_layout(out)
        ids = None if label.ids is None else tuple(label.ids.shape)
        if (not frames or len(frames) < 2 or not ids
                or ids != frames[:len(ids)]):
            raise ValueError(
                "seq_classification_error needs a sequence output "
                "[B, T, C] and integer labels [B] or [B, T]; got output "
                f"value {getattr(out.value, 'shape', None)}, label ids "
                f"{getattr(label.ids, 'shape', None)}")
        return _seq_classification_state(out, label)

    def result(self):
        return {"seq_classification_error": self._mean()}


@register_evaluator("last-column-auc")
class AucEvaluator(Evaluator):
    """Histogram AUC like the reference (AucEvaluator, Evaluator.h:155)."""

    BINS = 4096

    def start(self):
        self.pos = np.zeros(self.BINS)
        self.neg = np.zeros(self.BINS)

    def eval_batch(self, args):
        out, label = args[0], args[1]
        scores = self._rows(out)[:, -1]
        labels = self._label_rows(label)
        # optional third input: per-sample weight (adds w to the bin,
        # reference Evaluator.cpp statPos_/statNeg_ += w)
        w = (self._rows(args[2])[:, -1] if len(args) > 2
             else np.ones_like(scores, np.float64))
        idx = np.clip((scores * (self.BINS - 1)).astype(np.int64), 0, self.BINS - 1)
        np.add.at(self.pos, idx[labels == 1], w[labels == 1])
        np.add.at(self.neg, idx[labels != 1], w[labels != 1])

    def result(self):
        # trapezoidal over descending threshold
        tp = np.cumsum(self.pos[::-1])
        fp = np.cumsum(self.neg[::-1])
        tot_p, tot_n = tp[-1] if len(tp) else 0.0, fp[-1] if len(fp) else 0.0
        if tot_p == 0 or tot_n == 0:
            return {"auc": 0.0}
        tpr = np.concatenate([[0.0], tp / tot_p])
        fpr = np.concatenate([[0.0], fp / tot_n])
        auc = float(np.trapezoid(tpr, fpr))
        return {"auc": auc}

    def merge_state(self):
        return np.concatenate([self.pos, self.neg]).astype(np.float64)

    def load_state(self, vec):
        self.pos = np.asarray(vec[: self.BINS], np.float64)
        self.neg = np.asarray(vec[self.BINS :], np.float64)


@register_evaluator("rank-auc")
class RankAucEvaluator(Evaluator):
    """AUC over rank-model scores (ref: RankAucEvaluator, Evaluator.h:202):
    inputs = output score, click (label), optional pv (weight). Exact AUC
    over the accumulated (score, click, pv) triples."""

    def start(self):
        self.scores = []
        self.clicks = []
        self.pvs = []

    def eval_batch(self, args):
        out = self._rows(args[0])[:, -1]
        click = self._rows(args[1])[:, -1]
        pv = self._rows(args[2])[:, -1] if len(args) > 2 else np.ones_like(click)
        self.scores.append(out)
        self.clicks.append(click)
        self.pvs.append(pv)

    def result(self):
        if not self.scores:
            return {"rank_auc": 0.0}
        s = np.concatenate(self.scores)
        click = np.concatenate(self.clicks)
        pv = np.concatenate(self.pvs)
        # group by unique score so tied pos/neg pairs count 0.5 each
        # (order-independent AUC)
        uniq, inv = np.unique(s, return_inverse=True)
        pos_g = np.bincount(inv, weights=click, minlength=len(uniq))
        neg_g = np.bincount(inv, weights=pv - click, minlength=len(uniq))
        cum_neg_below = np.cumsum(neg_g) - neg_g   # strictly lower scores
        pairs_correct = float(np.sum(pos_g * (cum_neg_below + 0.5 * neg_g)))
        total_pairs = float(pos_g.sum() * neg_g.sum())
        return {"rank_auc": pairs_correct / total_pairs if total_pairs else 0.0}


@register_evaluator("precision_recall")
class PrecisionRecallEvaluator(Evaluator):
    def start(self):
        self.tp: Dict[int, float] = {}
        self.fp: Dict[int, float] = {}
        self.fn: Dict[int, float] = {}

    def eval_batch(self, args):
        out, label = args[0], args[1]
        probs = self._rows(out)
        labels = self._label_rows(label)
        pred = np.argmax(probs, axis=-1)
        for p, l in zip(pred, labels):
            p, l = int(p), int(l)
            if p == l:
                self.tp[l] = self.tp.get(l, 0) + 1
            else:
                self.fp[p] = self.fp.get(p, 0) + 1
                self.fn[l] = self.fn.get(l, 0) + 1

    def result(self):
        classes = set(self.tp) | set(self.fp) | set(self.fn)
        if self.cfg.positive_label >= 0:
            classes = {self.cfg.positive_label}
        precs, recs = [], []
        for c in classes:
            tp = self.tp.get(c, 0.0)
            fp = self.fp.get(c, 0.0)
            fn = self.fn.get(c, 0.0)
            precs.append(tp / max(tp + fp, 1.0))
            recs.append(tp / max(tp + fn, 1.0))
        p = float(np.mean(precs)) if precs else 0.0
        r = float(np.mean(recs)) if recs else 0.0
        f1 = 2 * p * r / max(p + r, 1e-9)
        return {"precision": p, "recall": r, "F1": f1}


@register_evaluator("pnpair")
class PnpairEvaluator(Evaluator):
    """Positive-negative pair ordering accuracy (ref Evaluator.h:308)."""

    def start(self):
        self.records: List = []

    def eval_batch(self, args):
        out, label = args[0], args[1]
        scores = self._rows(out)[:, -1]
        labels = self._label_rows(label)
        # optional third input: query id for grouping; fourth: weight
        if len(args) > 2:
            qids = self._label_rows(args[2])
        else:
            qids = np.zeros_like(labels)
        w = (self._rows(args[3])[:, -1] if len(args) > 3
             else np.ones_like(scores, np.float64))
        self.records.extend(
            zip(qids.tolist(), labels.tolist(), scores.tolist(), w.tolist()))

    def result(self):
        from collections import defaultdict

        by_q = defaultdict(list)
        for q, l, s, w in self.records:
            by_q[q].append((l, s, w))
        pos_minus_neg = 0.0
        total = 0.0
        CHUNK = 256  # bounds pair-walk temporaries to CHUNK*n entries
        for items in by_q.values():
            # vectorized pair walk in row chunks (semantics identical to
            # the reference's O(n^2) loop, PnpairEvaluator::stat: pair
            # weight = mean of the two samples' weights, ties 0.5) —
            # memory stays O(CHUNK*n) even when every record lands in one
            # group (the no-qid default)
            n = len(items)
            l = np.asarray([it[0] for it in items], np.float64)
            sc = np.asarray([it[1] for it in items], np.float64)
            w = np.asarray([it[2] for it in items], np.float64)
            col = np.arange(n)
            for i0 in range(0, n - 1, CHUNK):
                rows = np.arange(i0, min(i0 + CHUNK, n - 1))
                pair = col[None, :] > rows[:, None]          # j > i
                diff = pair & (l[None, :] != l[rows][:, None])
                if not diff.any():
                    continue
                ri, cj = np.nonzero(diff)
                iu, ju = rows[ri], cj
                pw = (w[iu] + w[ju]) / 2.0
                hi_is_i = l[iu] > l[ju]
                hi = np.where(hi_is_i, sc[iu], sc[ju])
                lo = np.where(hi_is_i, sc[ju], sc[iu])
                total += float(pw.sum())
                pos_minus_neg += float(pw[hi > lo].sum() + 0.5 * pw[hi == lo].sum())
        # raw total as the denominator: max(total, 1) would deflate the
        # metric whenever the total pair weight is < 1
        return {"pnpair_accuracy": pos_minus_neg / total if total > 0 else 0.0}


@register_evaluator("ctc_edit_distance")
class CTCErrorEvaluator(Evaluator):
    """Edit distance between CTC best-path decode and the label sequence
    (ref: CTCErrorEvaluator.cpp)."""

    def start(self):
        self.dist = 0.0
        self.total_labels = 0.0

    @staticmethod
    def _edit_distance(a, b) -> int:
        la, lb = len(a), len(b)
        dp = list(range(lb + 1))
        for i in range(1, la + 1):
            prev = dp[0]
            dp[0] = i
            for j in range(1, lb + 1):
                cur = dp[j]
                dp[j] = min(dp[j] + 1, dp[j - 1] + 1, prev + (a[i - 1] != b[j - 1]))
                prev = cur
        return dp[lb]

    def eval_batch(self, args):
        out, label = args[0], args[1]
        probs = np.asarray(out.value)  # [B, T, C] (blank = C-1)
        lens = np.asarray(out.seq_lengths)
        blank = probs.shape[-1] - 1
        label_ids = np.asarray(label.ids)
        label_lens = np.asarray(label.seq_lengths)
        for b in range(probs.shape[0]):
            path = np.argmax(probs[b, : lens[b]], axis=-1)
            decoded = []
            prev = -1
            for p in path:
                if p != prev and p != blank:
                    decoded.append(int(p))
                prev = p
            target = label_ids[b, : label_lens[b]].tolist()
            self.dist += self._edit_distance(decoded, target)
            self.total_labels += len(target)

    def result(self):
        return {"ctc_error_rate": self.dist / max(self.total_labels, 1.0)}

    def merge_state(self):
        return np.array([self.dist, self.total_labels], np.float64)

    def load_state(self, vec):
        self.dist, self.total_labels = float(vec[0]), float(vec[1])


@register_evaluator("chunk")
class ChunkEvaluator(Evaluator):
    """IOB/IOE/IOBES chunking F1 (ref: ChunkEvaluator.cpp)."""

    def start(self):
        self.correct = 0.0
        self.pred_chunks = 0.0
        self.label_chunks = 0.0

    def _extract_chunks(self, tags: List[int]):
        """tag = type * tagsPerType + posInScheme. IOB: 0=B,1=I; IOE: 0=I,
        1=E; IOBES: 0=B,1=I,2=E,3=S; 'other' = last tag id."""
        scheme = self.cfg.chunk_scheme
        n_types = self.cfg.num_chunk_types
        per = {"IOB": 2, "IOE": 2, "IOBES": 4, "plain": 1}[scheme]
        other = n_types * per
        chunks = []
        start = None
        ctype = None
        for i, t in enumerate(tags + [other]):
            if t >= other:
                tt, pos = None, None
            else:
                tt, pos = t // per, t % per
            begin = False
            end_prev = False
            if scheme == "IOB":
                begin = pos == 0
                end_prev = tt is None or (start is not None and (pos == 0 or tt != ctype))
            elif scheme == "IOE":
                begin = start is None and tt is not None
                end_prev = start is not None and (ctype != tt or (i > 0 and tags[i - 1] % per == 1))
            elif scheme == "IOBES":
                begin = pos in (0, 3)
                end_prev = tt is None or (start is not None and (pos in (0, 3) or tt != ctype))
            else:  # plain: every tag is its own chunk type, 'other' closes
                begin = tt is not None and tt != ctype
                end_prev = start is not None and tt != ctype
            if end_prev and start is not None:
                chunks.append((start, i - 1, ctype))
                start = None
            if begin and tt is not None:
                start = i
                ctype = tt
            elif tt is None:
                start = None
                ctype = None
        return set(chunks)

    def eval_batch(self, args):
        out, label = args[0], args[1]
        preds = self._label_rows(out)
        labels = self._label_rows(label)
        pred_chunks = self._extract_chunks([int(x) for x in preds])
        label_chunks = self._extract_chunks([int(x) for x in labels])
        self.correct += len(pred_chunks & label_chunks)
        self.pred_chunks += len(pred_chunks)
        self.label_chunks += len(label_chunks)

    def result(self):
        p = self.correct / max(self.pred_chunks, 1.0)
        r = self.correct / max(self.label_chunks, 1.0)
        return {"precision": p, "recall": r, "F1": 2 * p * r / max(p + r, 1e-9)}


class _PrinterEvaluator(Evaluator):
    def start(self):
        self.lines: List[str] = []

    def result(self):
        return {}

    def summary(self):
        return "\n".join(self.lines[-5:])


@register_evaluator("value_printer")
class ValuePrinterEvaluator(_PrinterEvaluator):
    def eval_batch(self, args):
        self.lines.append(str(self._rows(args[0])[:4]))


@register_evaluator("gradient_printer")
class GradientPrinterEvaluator(_PrinterEvaluator):
    def eval_batch(self, args):
        self.lines.append("<gradients not captured in functional mode>")


@register_evaluator("max_id_printer")
class MaxIdPrinterEvaluator(_PrinterEvaluator):
    def eval_batch(self, args):
        rows = self._rows(args[0])
        self.lines.append(str(np.argsort(-rows, axis=-1)[:4, : max(1, self.cfg.num_results)]))


@register_evaluator("max_frame_printer")
class MaxFramePrinterEvaluator(_PrinterEvaluator):
    def eval_batch(self, args):
        rows = self._rows(args[0])
        self.lines.append(str(rows.max(axis=-1)[:4]))


@register_evaluator("seq_text_printer")
class SeqTextPrinterEvaluator(_PrinterEvaluator):
    def eval_batch(self, args):
        arg = args[-1]
        ids = np.asarray(arg.ids) if arg.ids is not None else np.argmax(np.asarray(arg.value), -1)
        vocab = None
        if self.cfg.dict_file:
            try:
                with open(self.cfg.dict_file) as f:
                    vocab = [l.rstrip("\n") for l in f]
            except OSError:
                vocab = None
        for row in ids[:4]:
            toks = [vocab[t] if vocab and t < len(vocab) else str(int(t)) for t in np.atleast_1d(row)]
            line = (" " if self.cfg.delimited else "").join(toks)
            self.lines.append(line)
        if self.cfg.result_file:
            with open(self.cfg.result_file, "a") as f:
                for row in ids:
                    toks = [
                        vocab[t] if vocab and t < len(vocab) else str(int(t))
                        for t in np.atleast_1d(row)
                    ]
                    f.write((" " if self.cfg.delimited else "").join(toks) + "\n")


@register_evaluator("classification_error_printer")
class ClassificationErrorPrinterEvaluator(_PrinterEvaluator):
    def eval_batch(self, args):
        out, label = args[0], args[1]
        probs = self._rows(out)
        labels = self._label_rows(label)
        pred = np.argmax(probs, axis=-1)
        err = (pred != labels).astype(np.float32)
        self.lines.append(str(err[:16]))


class EvaluatorChain:
    """All configured evaluators of a model, fed from layer outputs."""

    def __init__(self, model: ModelConfig, names: Optional[List[str]] = None):
        self.model = model
        self.evaluators: List[Evaluator] = []
        # set by the trainer in multi-process runs when evaluators were fed
        # process-local rows: vec -> cross-process SUM of vec. Reading
        # results then merges sufficient statistics once — the reference's
        # distributeEval (Evaluator.h:81-82) — instead of gathering raw
        # activations every batch.
        self.merge_fn = None
        # names of the evaluators fed by add_states: a state computed
        # inside a jitted step over a mesh is already global and the same
        # on every process, so merge_fn must not sum it again
        self._in_step: set = set()
        # how often the statistic was computed inside the step, and how
        # often from layer outputs outside it: one an evaluator a batch
        self._device_batches = obs.registry().counter("eval.device_batches")
        self._host_batches = obs.registry().counter("eval.host_batches")
        for cfg in model.evaluators:
            if names is not None and cfg.name not in names:
                continue
            if cfg.type in evaluator_registry:
                self.evaluators.append(evaluator_registry.get(cfg.type)(cfg))

    @staticmethod
    def partition(evaluators: List[Evaluator]):
        """(mergeable, unmergeable) evaluators: mergeable ones carry
        summable state and can accumulate on local rows."""
        merge, gather = [], []
        for e in evaluators:
            (merge if e.merge_state() is not None else gather).append(e)
        return merge, gather

    @staticmethod
    def layers_for(evaluators: List[Evaluator]) -> List[str]:
        seen: List[str] = []
        for e in evaluators:
            for n in e.cfg.input_layers:
                if n not in seen:
                    seen.append(n)
        return seen

    def _merged(self, e: Evaluator) -> Evaluator:
        """A view of e with cross-process-merged state (e itself keeps
        accumulating local rows; merging at read time is idempotent)."""
        if self.merge_fn is None or e.cfg.name in self._in_step:
            return e
        vec = e.merge_state()
        if vec is None:
            return e
        clone = type(e)(e.cfg)
        clone.load_state(self.merge_fn(vec))
        return clone

    def __bool__(self) -> bool:
        return bool(self.evaluators)

    @property
    def needed_layers(self) -> List[str]:
        """Layer outputs the chain reads — multi-process runs gather only
        these to the host (distributeEval analog, Evaluator.h:81-82)."""
        return self.layers_for(self.evaluators)

    def start(self):
        for e in self.evaluators:
            e.start()

    @staticmethod
    def _inputs(e: Evaluator, outputs: Dict[str, Argument]):
        """The evaluator's input Arguments, or None where one is missing."""
        args = [outputs[n] for n in e.cfg.input_layers if n in outputs]
        return args if len(args) == len(e.cfg.input_layers) else None

    def batch_states(self, outputs: Dict[str, Argument]) -> Dict[str, jax.Array]:
        """{evaluator name: f32[k]} for the evaluators whose per-batch
        statistic is a traceable function of these layers — called on a
        train step's layer outputs inside its trace, before the step
        selects what to keep; the others are fed on the host."""
        states = {}
        for e in self.evaluators:
            args = self._inputs(e, outputs)
            if args is None:
                continue
            # `<type>:<name>`, as a layer's scope: a profile's device time
            # splits by evaluator too
            with jax.named_scope(f"{e.cfg.type}:{e.cfg.name}"):
                state = e.batch_state(args)
            if state is not None:
                states[e.cfg.name] = state
        return states

    def add_states(self, states: Dict[str, Any]) -> List[Evaluator]:
        """Accumulate the states a train step returned (per batch, or
        stacked); returns the evaluators that were not in the step."""
        rest = []
        for e in self.evaluators:
            state = states.get(e.cfg.name)
            if state is None:
                rest.append(e)
            else:
                e.add_state(state)
                self._in_step.add(e.cfg.name)
                self._device_batches.inc()
        return rest

    def eval_batch(self, outputs: Dict[str, Argument], only: Optional[List[Evaluator]] = None):
        for e in (self.evaluators if only is None else only):
            args = self._inputs(e, outputs)
            if args is not None:
                with stat_timer(f"eval/{e.cfg.type}"):
                    e.eval_batch(args)
                self._host_batches.inc()

    def summary(self) -> str:
        parts = []
        for e in self.evaluators:
            s = self._merged(e).summary()
            if s:
                parts.append(f"{e.cfg.name}: {s}")
        return "  ".join(parts)

    def results(self) -> Dict[str, float]:
        out = {}
        for e in self.evaluators:
            for k, v in self._merged(e).result().items():
                out[f"{e.cfg.name}.{k}"] = v
        return out
