"""Continuous-batching engine (paddle_tpu/serving/, doc/serving.md):
scheduler unit tests on the injectable-clock / fake-decode seam (slot
reuse after EOS, FIFO admit fairness, cancel/timeout/drain), greedy
prefill+decode parity vs ``SequenceGenerator`` golden outputs on the
same params, the chaos e2e (injected decode fault mid-load), the
``attention_gru_step`` ops seam vs the fused kernel, the
``bench.py serve --engine={static,continuous}`` A/B (the same tokens in
fewer device decode steps), and the ``paddle serve`` SIGTERM graceful-drain
subprocess e2e."""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.observability import metrics as obs
from paddle_tpu.observability import serving as slog
from paddle_tpu.observability.analyze import load_run
from paddle_tpu.serving import (
    Engine,
    FakeBackend,
    parse_decode_blocks,
    pick_block,
)
from paddle_tpu.utils import concurrency as cc

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    obs.registry().reset()
    yield
    obs.configure("")


def _results(futs, timeout=60.0):
    return [f.result(timeout=timeout) for f in futs]


# ------------------------------------------------------- scheduler units


def test_fifo_admission_and_slot_reuse_after_finish():
    """More requests than slots: admission order is strict FIFO and
    freed slots (EOS/budget) are reused — the total admitted across
    waves exceeds the slot count."""
    be = FakeBackend(slots=2, max_length=8)
    eng = Engine(be, request_timeout_s=30.0).start()
    futs = [eng.submit([2], max_new_tokens=1 + (i % 3), rid=f"r{i}")
            for i in range(7)]
    res = _results(futs)
    assert all(r.outcome == "ok" for r in res), [r.outcome for r in res]
    for i, r in enumerate(res):
        assert len(r.tokens) == 1 + (i % 3), (i, r.tokens)
    admitted = [rid for wave in be.admits for rid in wave]
    assert admitted == [f"r{i}" for i in range(7)]  # FIFO, no reorder
    assert len(be.admits) > 1  # slots were reused, not one static cohort
    assert eng.drain(timeout=30.0)


def test_eos_frees_slot_midstream():
    """A scripted EOS ends the sequence before its budget and frees the
    slot; the EOS token itself is delivered (the static path's lens
    semantics)."""
    eos_at = {"r0": 2}  # r0 emits eos as its 3rd token

    def token_fn(rid, i):
        return 1 if i == eos_at.get(rid, -1) else 5 + i

    be = FakeBackend(slots=1, max_length=16, eos=1, token_fn=token_fn)
    eng = Engine(be, request_timeout_s=30.0).start()
    r0 = eng.submit([2], max_new_tokens=10, rid="r0").result(timeout=30.0)
    r1 = eng.submit([2], max_new_tokens=2, rid="r1").result(timeout=30.0)
    assert r0.outcome == "ok" and r0.tokens == [5, 6, 1]
    assert r1.outcome == "ok" and len(r1.tokens) == 2
    assert eng.drain(timeout=30.0)


def test_injectable_clock_wall_deadlines():
    """Queued-request timeout and in-flight timeout run on the
    injectable clock (wall time, not virtual): advancing the fake clock
    past the deadline frees the queue entry / the slot at the next
    iteration boundary with outcome=timeout."""
    now = [0.0]
    # a slow backend that parks the only slot long enough for the fake
    # clock to expire it (1 ms of real time per step, 1000-step budget)
    be = FakeBackend(slots=1, max_length=1000, step_delay_s=0.001)
    eng = Engine(be, request_timeout_s=5.0, clock=lambda: now[0],
                 idle_poll_s=0.005)
    eng.start()
    blocker = eng.submit([2], max_new_tokens=1000, rid="blocker")
    queued = eng.submit([2], max_new_tokens=1, rid="queued")
    time.sleep(0.05)  # let the loop admit the blocker
    now[0] = 6.0      # past both deadlines
    rq = queued.result(timeout=30.0)
    rb = blocker.result(timeout=30.0)
    assert rq.outcome == "timeout", rq
    assert rb.outcome == "timeout", rb
    # the engine is still serving after the sweep
    now[0] = 7.0
    ok = eng.submit([2], max_new_tokens=1, rid="after").result(timeout=30.0)
    assert ok.outcome == "ok"
    assert eng.drain(timeout=30.0)


def test_cancel_queued_and_inflight():
    be = FakeBackend(slots=1, max_length=64, step_delay_s=0.002)
    eng = Engine(be, request_timeout_s=30.0).start()
    f0 = eng.submit([2], max_new_tokens=64, rid="long")
    f1 = eng.submit([2], max_new_tokens=1, rid="queued")
    assert eng.cancel("queued") is True
    assert eng.cancel("long") is True
    assert eng.cancel("nope") is False
    r0, r1 = f0.result(timeout=30.0), f1.result(timeout=30.0)
    assert r1.outcome == "cancelled"
    assert r0.outcome in ("cancelled", "ok")  # may have finished first
    nxt = eng.submit([2], max_new_tokens=1, rid="next").result(timeout=30.0)
    assert nxt.outcome == "ok"  # the cancelled slot was reclaimed
    assert eng.drain(timeout=30.0)


def test_drain_finishes_inflight_rejects_queued_and_new():
    be = FakeBackend(slots=1, max_length=32, step_delay_s=0.002)
    eng = Engine(be, request_timeout_s=30.0).start()
    inflight = eng.submit([2], max_new_tokens=20, rid="inflight")
    queued = [eng.submit([2], rid=f"q{i}") for i in range(3)]
    time.sleep(0.03)  # let the loop admit `inflight`
    assert eng.drain(timeout=30.0)
    assert inflight.result(timeout=1.0).outcome == "ok"
    assert {f.result(timeout=1.0).outcome for f in queued} <= {
        "rejected", "ok"}
    assert any(f.result(timeout=1.0).outcome == "rejected" for f in queued)
    late = eng.submit([2], rid="late").result(timeout=1.0)
    assert late.outcome == "rejected"


def test_drain_rejection_counts_arrived_once():
    """A queued request rejected by the drain was already counted as
    arrived at enqueue — the window must not double-count it."""
    be = FakeBackend(slots=1, max_length=32, step_delay_s=0.002)
    eng = Engine(be, request_timeout_s=30.0).start()
    futs = [eng.submit([2], max_new_tokens=20, rid=f"r{i}") for i in range(4)]
    time.sleep(0.03)
    assert eng.drain(timeout=30.0)
    _results(futs, timeout=1.0)
    w = eng.window_roll(offered_rps=1.0, rung=0)
    assert w["arrived"] == 4, w
    assert w["completed"] + w["rejected"] + w["timeouts"] == 4, w


def test_zero_budget_is_a_legal_answer():
    """max_new_tokens=0 means THE EMPTY GENERATION (0 is not an unset
    sentinel): outcome=ok, zero tokens, no slot consumed."""
    be = FakeBackend(slots=1, max_length=8)
    eng = Engine(be, request_timeout_s=30.0).start()
    r = eng.submit([2, 3], max_new_tokens=0, rid="empty").result(timeout=30.0)
    assert r.outcome == "ok" and r.tokens == []
    # None still means "the graph's max_length"
    full = eng.submit([2], rid="full").result(timeout=30.0)
    assert full.outcome == "ok" and len(full.tokens) == 8
    assert eng.drain(timeout=30.0)


def test_queue_cap_rejects_at_submit():
    be = FakeBackend(slots=1, max_length=64, step_delay_s=0.005)
    eng = Engine(be, queue_cap=1, request_timeout_s=30.0).start()
    futs = [eng.submit([2], max_new_tokens=30, rid=f"r{i}") for i in range(5)]
    outcomes = [f.result(timeout=60.0).outcome for f in futs]
    assert "rejected" in outcomes, outcomes
    assert outcomes[0] == "ok"
    assert eng.drain(timeout=30.0)


def test_chaos_decode_fault_midload_engine_survives(tmp_path):
    """Injected decode fault mid-load: the in-flight cohort resolves
    outcome=error, the engine stays alive, later requests complete, and
    every emitted record passes validate_record."""
    obs.configure(str(tmp_path))
    be = FakeBackend(slots=2, max_length=8, fail_at_launch=2,
                     step_delay_s=0.001)
    eng = Engine(be, request_timeout_s=30.0).start()
    first = [eng.submit([2], max_new_tokens=4, rid=f"a{i}") for i in range(4)]
    outcomes = [f.result(timeout=60.0).outcome for f in first]
    assert "error" in outcomes, outcomes
    later = [eng.submit([2], max_new_tokens=2, rid=f"b{i}") for i in range(3)]
    assert all(f.result(timeout=60.0).outcome == "ok" for f in later)
    assert eng.drain(timeout=30.0)
    eng.window_roll(offered_rps=1.0, rung=0)
    obs.emit("run_end", status="completed")
    obs.flush()
    recs = [r for recs in load_run(str(tmp_path)).values() for r in recs]
    for rec in recs:
        assert not obs.validate_record(rec), (rec, obs.validate_record(rec))
    reqs = [r for r in recs if r["kind"] == "request"]
    assert {r["outcome"] for r in reqs} >= {"ok", "error"}
    assert all(r.get("engine") == "continuous" for r in reqs)
    errs = [r for r in reqs if r["outcome"] == "error"]
    assert errs and all("decode" in (r.get("error") or "").lower()
                        or "injected" in (r.get("error") or "").lower()
                        for r in errs)


def test_realtime_ttft_is_midstream():
    """TTFT comes from the first token's readback, mid-sequence — for a
    multi-token request t_first_token strictly precedes t_finish (the
    static path's first-token==finish degenerate case is gone)."""
    be = FakeBackend(slots=1, max_length=32, step_delay_s=0.002)
    eng = Engine(be, request_timeout_s=30.0)
    captured = []
    orig = eng._finish_locked

    def spy(req, outcome, now, error=None):
        captured.append(req)
        return orig(req, outcome, now, error=error)

    eng._finish_locked = spy
    eng.start()
    assert eng.submit([2], max_new_tokens=10,
                      rid="r1").result(timeout=30.0).outcome == "ok"
    assert eng.drain(timeout=30.0)
    (req,) = [r for r in captured if r.rid == "r1"]
    assert 0 <= req.t_first_token < req.t_finish


# ---------------------------------------------- pipelined loop semantics


def test_pick_block_policy_and_ladder_parse():
    """The adaptive decode-block policy (doc/serving.md): budget caps,
    queue/TTFT pressure picks the smallest rung that amortizes the
    measured overhead, quiet picks the top rung."""
    assert parse_decode_blocks("8,4,2,1,4") == (1, 2, 4, 8)
    assert parse_decode_blocks(6) == (6,)
    assert parse_decode_blocks(None) == (1,)
    assert pick_block((4,), 1, True, 1.0, 0.0) == 4      # one rung: no choice
    assert pick_block((1, 2, 4, 8), 100, False, 0.0, 0.0) == 8   # quiet: top
    assert pick_block((1, 2, 4, 8), 3, False, 0.0, 0.0) == 2     # budget cap
    # pressure + measurements: smallest rung keeping overhead under the
    # share; overhead-dominated steps to the top; unmeasured stays low
    assert pick_block((1, 2, 4, 8), 100, True, 0.001, 0.001) == 2
    assert pick_block((1, 2, 4, 8), 100, True, 0.01, 0.001) == 8
    assert pick_block((1, 2, 4, 8), 100, True, 0.0, 0.0) == 1


def _run_workload(pipeline, n=12, slots=3):
    """One seeded schedule_requests workload through a fresh engine;
    returns ({rid: (outcome, tokens)}, flattened admission order)."""
    be = FakeBackend(slots=slots, max_length=16)
    eng = Engine(be, request_timeout_s=60.0, pipeline=pipeline).start()
    reqs = slog.schedule_requests(
        50.0, n, 3, prompt_fn=lambda rng, i: [2, 3],
        budget_fn=lambda rng, i: 1 + int(rng.randint(0, 5)),
    )
    futs = {r.rid: eng.submit(r.prompt, max_new_tokens=r.max_new, rid=r.rid)
            for r in reqs}
    res = {rid: f.result(timeout=60.0) for rid, f in futs.items()}
    admits = [rid for wave in be.admits for rid in wave]
    assert eng.drain(timeout=30.0)
    return {rid: (r.outcome, r.tokens) for rid, r in res.items()}, admits


def test_golden_pipelined_equals_blocking_streams():
    """THE golden test: on the same seeded schedule_requests workload
    the pipelined engine emits the IDENTICAL per-request token streams
    and outcomes as the PR-12 blocking loop — and the same FIFO
    admission order."""
    got_p, admits_p = _run_workload(True)
    got_b, admits_b = _run_workload(False)
    assert got_p == got_b
    assert admits_p == admits_b
    assert all(o == "ok" for o, _ in got_p.values())


def test_golden_pipelined_equals_blocking_cancel_timeout_drain_fault():
    """The edge paths, both loops: cancel lands cancelled, the
    injectable clock expires queued AND in-flight requests, drain
    completes in-flight and rejects queued, and a faulted launch errors
    its cohort while the engine keeps serving — identical outcomes."""
    results = {}
    for pipeline in (True, False):
        out = {}
        # cancel: the queued request is cancelled before its admission
        be = FakeBackend(slots=1, max_length=64, step_delay_s=0.005)
        eng = Engine(be, request_timeout_s=30.0, pipeline=pipeline).start()
        blk = eng.submit([2], max_new_tokens=40, rid="blk")
        q1 = eng.submit([2], max_new_tokens=1, rid="q1")
        assert eng.cancel("q1") is True
        out["cancel"] = q1.result(timeout=30.0).outcome
        assert blk.result(timeout=30.0).outcome == "ok"
        assert eng.drain(timeout=30.0)
        # timeout: fake clock expires the in-flight slot and the queue
        now = [0.0]
        be = FakeBackend(slots=1, max_length=1000, step_delay_s=0.001)
        eng = Engine(be, request_timeout_s=5.0, clock=lambda: now[0],
                     idle_poll_s=0.005, pipeline=pipeline).start()
        b2 = eng.submit([2], max_new_tokens=1000, rid="b2")
        q2 = eng.submit([2], max_new_tokens=1, rid="q2")
        time.sleep(0.05)
        now[0] = 6.0
        out["timeout"] = (q2.result(timeout=30.0).outcome,
                          b2.result(timeout=30.0).outcome)
        assert eng.drain(timeout=30.0)
        # drain: in-flight finishes, queued rejected. The drain lands
        # once the first request HOLDS the one slot (not after a sleep
        # it may have finished in), with 0.6 s of decode still ahead
        be = FakeBackend(slots=1, max_length=32, step_delay_s=0.02)
        eng = Engine(be, request_timeout_s=30.0, pipeline=pipeline).start()
        inflight = eng.submit([2], max_new_tokens=30, rid="in")
        queued = [eng.submit([2], rid=f"dq{i}") for i in range(3)]
        while not be.admits:
            time.sleep(0.001)
        assert eng.drain(timeout=30.0)
        out["drain_inflight"] = inflight.result(timeout=1.0).outcome
        out["drain_rejected"] = sorted(
            f.result(timeout=1.0).outcome for f in queued)
        # fault: launch 3 faults with both requests in flight
        be = FakeBackend(slots=2, max_length=8, step_delay_s=0.02,
                         fail_at_launch=3)
        eng = Engine(be, request_timeout_s=30.0, pipeline=pipeline).start()
        f0 = eng.submit([2], max_new_tokens=6, rid="f0")
        f1 = eng.submit([2], max_new_tokens=6, rid="f1")
        out["fault"] = sorted((f0.result(timeout=30.0).outcome,
                               f1.result(timeout=30.0).outcome))
        ok = eng.submit([2], max_new_tokens=1, rid="after")
        out["fault_after"] = ok.result(timeout=30.0).outcome
        assert eng.drain(timeout=30.0)
        results[pipeline] = out
    assert results[True] == results[False], results
    assert results[True]["cancel"] == "cancelled"
    assert results[True]["timeout"] == ("timeout", "timeout")
    assert results[True]["drain_inflight"] == "ok"
    assert "rejected" in results[True]["drain_rejected"]
    assert results[True]["fault"] == ["error", "error"]
    assert results[True]["fault_after"] == "ok"


def test_adaptive_ladder_matches_single_block():
    """The decode-block ladder is a perf knob, not a semantics knob:
    the adaptive engine's outputs equal the single-block engine's."""
    outs = {}
    for spec in ("1", "1,2,4,8"):
        be = FakeBackend(slots=2, max_length=16, chunk=spec)
        eng = Engine(be, request_timeout_s=30.0).start()
        futs = [eng.submit([2], max_new_tokens=3 + i, rid=f"r{i}")
                for i in range(5)]
        outs[spec] = [f.result(timeout=30.0).tokens for f in futs]
        assert eng.drain(timeout=30.0)
    assert outs["1"] == outs["1,2,4,8"]


class AsyncDeviceBackend(FakeBackend):
    """A FakeBackend whose launches run on a WALL-CLOCK deadline — the
    model of a real accelerator on a small CI host: an in-flight launch
    occupies no host core (sleep), so host work genuinely overlaps it.
    ``host_cost_s`` burns real host time at collect (the readback /
    bookkeeping the pipelined loop hides behind the next launch)."""

    def __init__(self, *a, launch_s=0.003, host_cost_s=0.0015, **kw):
        super().__init__(*a, **kw)
        self.launch_s = float(launch_s)
        self.host_cost_s = float(host_cost_s)
        self._ready_at = []

    def dispatch(self, block=None):
        now = cc.monotonic()
        start = max(now, self._ready_at[-1] if self._ready_at else now)
        super().dispatch(block=block)
        self._ready_at.append(start + self.launch_s)

    def collect(self):
        ready = self._ready_at.pop(0)
        now = cc.monotonic()
        if now < ready:
            cc.sleep(ready - now)
        out = super().collect()
        t0 = cc.monotonic()
        while cc.monotonic() - t0 < self.host_cost_s:
            pass  # busy host work, deliberately un-sleepable
        return out

    def reset(self):
        super().reset()
        self._ready_at = []


def test_ab_pipelined_overlap_acceptance(tmp_path):
    """THE overlap A/B, device-modeled so it holds on a 1-core CI box
    (on the CPU backend "device" work shares the host's core, so real
    overlap is physically impossible there — doc/serving.md): the
    pipelined engine on the same seeded mixed-length overload ladder
    beats the blocking loop on goodput, its serve_window host_share
    (the device-waits-for-host share) drops, overlap_s is accounted,
    and `paddle compare` of the two run dirs lands verdict IMPROVED
    with exit 0."""
    from paddle_tpu.observability import compare

    budget_fn = lambda rng, i: 12 if rng.rand() < 0.2 else 2 + int(
        rng.randint(0, 4))
    windows = {}
    for mode, pipeline in (("off", False), ("on", True)):
        obs.registry().reset()
        obs.configure(str(tmp_path / mode))
        from paddle_tpu.serving import drive_rung

        be = AsyncDeviceBackend(slots=2, max_length=12)
        eng = Engine(be, request_timeout_s=60.0, pipeline=pipeline).start()
        ws = []
        for rung, rate in enumerate((200.0, 400.0)):
            reqs = slog.schedule_requests(rate, 16, 7 + rung, rung=rung,
                                          prompt_fn=lambda rng, i: [2, 3],
                                          budget_fn=budget_fn)
            ws.append(drive_rung(eng, reqs, rate_rps=rate, rung=rung))
        assert eng.drain(timeout=60.0)
        obs.emit("run_end", status="completed")
        obs.flush()
        windows[mode] = ws
    for w_off, w_on in zip(windows["off"], windows["on"]):
        assert w_on["goodput_tok_s"] > w_off["goodput_tok_s"], (w_off, w_on)
        assert w_on["pipeline"] == "on" and w_off["pipeline"] == "off"
        assert w_on.get("overlap_s", 0.0) > 0.0
    # host/dispatch share down in aggregate (per-rung shares are small
    # in this device-heavy model; the direction is the structural claim)
    mean = lambda ws: sum(w["host_share"] for w in ws) / len(ws)
    assert mean(windows["on"]) < mean(windows["off"]), windows
    doc = compare.compare(compare.load_side(str(tmp_path / "off")),
                          compare.load_side(str(tmp_path / "on")),
                          threshold=0.15)
    assert doc["verdict"] == "IMPROVED", doc
    assert any("goodput_tok_s" in m for m in doc["improvements"]), doc
    assert compare.main([str(tmp_path / "off"), str(tmp_path / "on"),
                         "--threshold", "0.15"]) == 0


# ----------------------------------------------------- jax decode parity


@pytest.fixture(scope="module")
def tiny_gen_machine():
    from paddle_tpu.flagship import nmt_gen_config
    from paddle_tpu.graph import GradientMachine
    from paddle_tpu.graph.machine import compute_dtype_of

    tc = nmt_gen_config(vocab=50, dim=16, beam_size=1, max_length=8,
                        dtype="float32", batch_size=2)
    gm = GradientMachine(tc.model_config,
                         compute_dtype=compute_dtype_of(tc.opt_config))
    return tc, gm, gm.init_params(seed=1)


def test_plan_gates_and_reasons(tiny_gen_machine):
    from paddle_tpu.flagship import nmt_config
    from paddle_tpu.graph import GradientMachine
    from paddle_tpu.graph.decode_step import plan_of

    _, gm, _ = tiny_gen_machine
    plan, reason = plan_of(gm)
    assert plan is not None and reason == ""
    assert plan.score_layer and plan.max_length == 8
    # a training graph has no generator: refused with the reason
    train_tc = nmt_config(vocab=50, dim=16, batch_size=2)
    plan2, reason2 = plan_of(GradientMachine(train_tc.model_config))
    assert plan2 is None and "generator" in reason2


def test_engine_matches_sequence_generator_golden(tiny_gen_machine):
    """Greedy slot decode == SequenceGenerator at beam_size=1, token
    for token, on the same params — the engine subsumes the embedding
    API for concurrent use (its documented adapter contract). Pinned
    across the pipelined loop, the blocking loop, AND the
    --serve_fused_step decoder: pipelined == blocking == fused ==
    SequenceGenerator greedy."""
    from paddle_tpu import api
    from paddle_tpu.graph import make_seq

    tc, gm, params = tiny_gen_machine
    am = api.GradientMachine(tc.model_config)
    am.params = params
    am._core = gm  # the EXACT same machine + params on both paths
    sg = am.asSequenceGenerator()
    rng = np.random.RandomState(7)
    prompts = [rng.randint(2, 50, size=rng.randint(1, 5)).astype(np.int32)
               for _ in range(4)]
    T = 4
    ids = np.zeros((4, T), np.int32)
    lens = np.zeros((4,), np.int32)
    for i, p in enumerate(prompts):
        ids[i, : len(p)] = p
        lens[i] = len(p)
    golden = [r[0]["ids"] for r in sg.generate(
        {"source_language_word": make_seq(None, lens, ids=ids)})]

    for pipeline, fused in ((True, False), (False, False), (True, True)):
        eng = am.asDecodeEngine(slots=3, prompt_tokens=T, pipeline=pipeline,
                                fused_step=fused).start()
        futs = [eng.submit(p.tolist(), rid=f"g{i}")
                for i, p in enumerate(prompts)]
        out = [f.result(timeout=120.0).tokens for f in futs]
        assert out == golden, (pipeline, fused)
        assert eng.drain(timeout=60.0)


def test_fused_step_refuses_off_template_models():
    """--serve_fused_step is an explicit request: a step graph outside
    the attention-GRU template refuses loudly with the reason instead
    of silently serving different math."""
    from paddle_tpu.flagship import nmt_gen_config
    from paddle_tpu.graph import GradientMachine
    from paddle_tpu.graph.decode_step import plan_fused_step, plan_of
    from paddle_tpu.graph.machine import compute_dtype_of
    from paddle_tpu.serving.jax_backend import (
        JaxDecodeBackend, UnsupportedModelError,
    )

    tc = nmt_gen_config(vocab=50, dim=16, beam_size=1, max_length=8,
                        dtype="float32", batch_size=2)
    gm = GradientMachine(tc.model_config,
                        compute_dtype=compute_dtype_of(tc.opt_config))
    plan, _ = plan_of(gm)
    fp, why = plan_fused_step(gm, plan)
    assert fp is not None, why
    assert fp["D"] == 16 and fp["vocab"] == 50
    # reduced compute precision: the fused math is f32, so parity with
    # the bf16 graph walk cannot be guaranteed — refused with the reason
    import jax.numpy as jnp

    gm_bf16 = GradientMachine(tc.model_config, compute_dtype=jnp.bfloat16)
    plan_bf16, _ = plan_of(gm_bf16)
    fp_bf16, why_bf16 = plan_fused_step(gm_bf16, plan_bf16)
    assert fp_bf16 is None and "float32" in why_bf16
    # de-template the gru activation: the matcher must refuse with the
    # reason, and the backend must raise it under the explicit flag
    gm.network.layer_map[plan.memories[0].layer_name].active_type = "relu"
    fp2, why2 = plan_fused_step(gm, plan)
    assert fp2 is None and "activations" in why2
    with pytest.raises(UnsupportedModelError, match="serve_fused_step"):
        JaxDecodeBackend(gm, gm.init_params(seed=1), slots=2,
                         prompt_tokens=4, fused_step=True)


WARM_SERVE_SCRIPT = """
import json, sys
cache_dir, run_dir = sys.argv[1], sys.argv[2]
# the cache must be enabled BEFORE anything touches jax: this jax
# version freezes the use-the-cache decision at first compile — the
# same ordering paddle_tpu.serving.frontend.main uses for the flag
from paddle_tpu.observability.compile_log import enable_compile_cache
assert enable_compile_cache(cache_dir)
from paddle_tpu.observability import metrics as obs
obs.configure(run_dir)
import jax
from paddle_tpu.flagship import nmt_gen_config
from paddle_tpu.graph import GradientMachine
from paddle_tpu.graph.machine import compute_dtype_of
from paddle_tpu.observability.compile_log import CompileRegistry
from paddle_tpu.serving import Engine
from paddle_tpu.serving.jax_backend import JaxDecodeBackend
tc = nmt_gen_config(vocab=50, dim=16, beam_size=1, max_length=8,
                    dtype="float32", batch_size=2)
gm = GradientMachine(tc.model_config,
                     compute_dtype=compute_dtype_of(tc.opt_config))
params = gm.init_params(seed=1)
registry = CompileRegistry(device_kind=jax.devices()[0].device_kind)
be = JaxDecodeBackend(gm, params, slots=2, prompt_tokens=4,
                      decode_block="1,2", registry=registry)
eng = Engine(be, request_timeout_s=60.0).start()
assert eng.drain(timeout=60.0)
obs.emit("run_end", status="completed")
obs.flush()
print(json.dumps({"warmup_s": eng.warmup_s}))
"""


def test_serve_warmup_compile_cache_hits(tmp_path):
    """--compile_cache_dir through the engine warmup (ROADMAP item 5
    applied to serving): a warm RESTART's serve_prefill/serve_decode
    compiles land with cache_hit=true and time-to-first-token-ready
    (Engine.start()'s warmup) drops below cold. Two fresh processes
    sharing the cache dir — the restart the elastic machinery makes
    frequent."""
    script = tmp_path / "warm_serve.py"
    script.write_text(WARM_SERVE_SCRIPT)
    warmup_s = {}
    for phase in ("cold", "warm"):
        out = subprocess.run(
            [sys.executable, str(script), str(tmp_path / "cache"),
             str(tmp_path / phase)],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
            cwd=REPO,
        )
        assert out.returncode == 0, out.stderr
        warmup_s[phase] = json.loads(out.stdout.splitlines()[-1])["warmup_s"]
    sums = {}
    for phase in ("cold", "warm"):
        recs = [r for rs in load_run(str(tmp_path / phase)).values()
                for r in rs]
        compiles = [r for r in recs if r["kind"] == "compile"
                    and r["group"] in ("serve_prefill", "serve_decode")]
        assert {c["group"] for c in compiles} == {"serve_prefill",
                                                 "serve_decode"}
        assert all(c["recompiles"] == 0 for c in compiles), compiles
        hits = [c.get("cache_hit") for c in compiles]
        assert all(h is (phase == "warm") for h in hits), (phase, compiles)
        sums[phase] = sum(c.get("compile_s", 0.0) + c.get("trace_s", 0.0)
                          for c in compiles)
    assert warmup_s["warm"] < warmup_s["cold"], warmup_s
    assert sums["warm"] < sums["cold"], sums


def test_decode_block_and_budget_on_device(tiny_gen_machine):
    """decode_block>1 micro-steps per launch: budgets still land
    exactly (device-side steps/budget termination), and outputs match
    the block=1 engine."""
    from paddle_tpu.serving.jax_backend import JaxDecodeBackend

    _, gm, params = tiny_gen_machine
    outs = {}
    for block in (1, 3):
        be = JaxDecodeBackend(gm, params, slots=2, prompt_tokens=4,
                              decode_block=block)
        eng = Engine(be, request_timeout_s=60.0).start()
        futs = [eng.submit([5 + i, 9], max_new_tokens=1 + i, rid=f"r{i}")
                for i in range(4)]
        res = _results(futs, timeout=120.0)
        assert all(r.outcome == "ok" for r in res)
        for i, r in enumerate(res):
            assert len(r.tokens) == 1 + i
        outs[block] = [r.tokens for r in res]
        assert eng.drain(timeout=60.0)
    assert outs[1] == outs[3]


def test_unsupported_model_refused_with_reason():
    from paddle_tpu.flagship import nmt_config
    from paddle_tpu.graph import GradientMachine
    from paddle_tpu.serving.jax_backend import (
        JaxDecodeBackend, UnsupportedModelError,
    )

    tc = nmt_config(vocab=50, dim=16, batch_size=2)
    gm = GradientMachine(tc.model_config)
    with pytest.raises(UnsupportedModelError, match="generator"):
        JaxDecodeBackend(gm, gm.init_params(seed=1), slots=2,
                         prompt_tokens=4)


def test_attention_gru_step_matches_fused_kernel():
    """The ops seam: T sequential attention_gru_step calls reproduce
    the fused kernel's whole-loop output (interpret mode) — the
    per-step math a TPU serve_decode kernel must implement."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_attention_gru import (
        attention_gru_step, fused_attention_gru,
    )

    rng = np.random.RandomState(0)
    Te, Td, B, D, E = 5, 4, 3, 8, 16
    r = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32) * 0.3)
    ep, ev = r(Te, B, D), r(Te, B, E)
    em = jnp.asarray(
        (rng.rand(Te, B, 1) > 0.2).astype(np.float32)).at[0].set(1.0)
    xw, h0 = r(Td, B, 3 * D), r(B, D)
    wa, ba, v, wctx, wg = r(D, D), r(1, D), r(1, D), r(E, 3 * D), r(D, 3 * D)
    dmask = jnp.ones((Td, B, 1), jnp.float32)
    ys = fused_attention_gru(ep, ev, em, xw, dmask, h0, wa, ba, v, wctx, wg,
                             ("tanh", "sigmoid"), True)
    h = h0
    for t in range(Td):
        h = attention_gru_step(h, ep, ev, em, xw[t], wa, ba, v, wctx, wg)
        np.testing.assert_allclose(np.asarray(ys[t], np.float32),
                                   np.asarray(h, np.float32),
                                   rtol=1e-5, atol=1e-5)


# -------------------------------------------------- bench A/B acceptance


def _bench(monkeypatch, tmp_path):
    monkeypatch.delenv("PADDLE_TPU_BENCH_METRICS_DIR", raising=False)
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_REQUESTS", "16")
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_MIXED_LEN", "1")
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_SEED", "0")
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    return bench


def test_bench_serve_continuous_e2e_acceptance(tmp_path, monkeypatch,
                                               capsys):
    """The acceptance path: `bench.py serve --engine=continuous` on the
    CPU backend completes >= 3 rungs, serve_decode (and serve_prefill)
    compile exactly ONCE with recompiles=0 after warmup, every record
    validates, and serve-report renders the run."""
    bench = _bench(monkeypatch, tmp_path)
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_DIR", str(tmp_path))
    value, extras = bench.bench_serve(B=2, T=4, vocab=50, dim=16,
                                      beam_size=1, max_length=8,
                                      dtype="float32", engine="continuous")
    obs.emit("run_end", status="completed")
    obs.flush()
    assert value > 0
    assert len(extras["rungs"]) >= 3
    assert extras["engine"] == "continuous"
    assert all(r["engine"] == "continuous" for r in extras["rungs"])

    recs = [r for rs in load_run(str(tmp_path)).values() for r in rs]
    for rec in recs:
        assert not obs.validate_record(rec), (rec, obs.validate_record(rec))
    compiles = {}
    for r in recs:
        if r["kind"] == "compile" and r["group"] in ("serve_decode",
                                                     "serve_prefill"):
            compiles.setdefault(r["group"], []).append(r)
    assert set(compiles) == {"serve_decode", "serve_prefill"}
    for group, rows in compiles.items():
        assert len(rows) == 1, (group, rows)      # ONE signature each
        assert rows[0]["recompiles"] == 0, (group, rows)
    wins = [r for r in recs if r["kind"] == "serve_window"]
    assert wins and all(w["engine"] == "continuous" for w in wins)

    assert slog.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    rows = [ln for ln in out.splitlines()
            if ln.strip() and ln.strip().split()[0].isdigit()]
    assert len(rows) >= 3
    assert "serve_decode" in out and "recompiles after warmup: 0" in out


def test_ab_compare_continuous_beats_static_at_knee(tmp_path, monkeypatch):
    """THE A/B, held to what a loaded CPU cannot move: both engines get
    the same seeded arrival schedule and mixed-length budgets at pinned
    rates, both complete it and deliver the same tokens, and the
    continuous engine spends fewer device decode steps on it (launches
    times the steps a launch runs, from the serve_window records).
    Which engine is FASTER is for a serve cell on the chip to say, not
    this host's clock."""
    from paddle_tpu.observability import compare

    bench = _bench(monkeypatch, tmp_path)
    block, max_length = 16, 64
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_BLOCK", str(block))
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_REQUESTS", "24")
    # this A/B pins the BATCHING-POLICY difference (run-to-completion
    # vs iteration-level scheduling), so the engine runs the serial
    # loop: with budgets <= the decode block the no-waste guard
    # disables overlap anyway
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_PIPELINE", "off")
    kw = dict(B=4, T=8, vocab=1000, dim=128, beam_size=1,
              max_length=max_length, dtype="float32")
    # the A/B regime is DEEP OVERLOAD: rates pinned at 2.5/5/10x the
    # static engine's capacity (a quick calibration pass), where every
    # cohort is full and run-to-completion pays max_length decode steps
    # for a cohort whose requests mostly want an eighth of that
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_DIR", str(tmp_path / "cal"))
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_RATES", "1.0")
    _, cal = bench.bench_serve(engine="static", n_requests=1, **kw)
    cap = cal["capacity_rps"]
    rates = ",".join(str(round(f * cap, 4)) for f in (2.5, 5.0, 10.0))
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_RATES", rates)
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_DIR",
                       str(tmp_path / "static"))
    vs, es = bench.bench_serve(engine="static", **kw)
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_DIR", str(tmp_path / "cont"))
    vc, ec = bench.bench_serve(engine="continuous", **kw)
    obs.configure("")

    def windows(side):
        recs = [r for rs in load_run(str(tmp_path / side)).values()
                for r in rs]
        return sorted((r for r in recs if r["kind"] == "serve_window"),
                      key=lambda w: w["rung"])

    w_static, w_cont = windows("static"), windows("cont")
    assert len(w_static) == len(w_cont) == 3
    for ws, wc in zip(w_static, w_cont):
        assert ws["offered_rps"] == wc["offered_rps"]
        for w in (ws, wc):
            assert w["arrived"] == w["completed"] == 24, w
        # the same requests with the same budgets: the same tokens
        assert ws["gen_tokens"] == wc["gen_tokens"], (ws, wc)
        # a static launch decodes until its whole cohort has ended
        # (max_length steps: the untrained model emits no EOS), a
        # continuous launch runs one decode block
        assert wc["launches"] * block < ws["launches"] * max_length, (ws, wc)

    # and `paddle compare` joins the two artifacts rung by rung on the
    # offered load: a goodput key per rung, none left on one side only
    a = tmp_path / "A.json"
    b = tmp_path / "B.json"
    metric = "serve_cpu_smoke_goodput_tokens_per_sec"
    a.write_text(json.dumps(dict(metric=metric, value=round(vs, 1), **es)))
    b.write_text(json.dumps(dict(metric=metric, value=round(vc, 1), **ec)))
    doc = compare.compare(compare.load_side(str(a)),
                          compare.load_side(str(b)), threshold=0.2)
    joined = [m["metric"] for m in doc["metrics"]
              if m["metric"].endswith("goodput_tok_s")]
    assert len(joined) == 3, joined
    assert not [k for k in doc["only_a"] + doc["only_b"]
                if "goodput_tok_s" in k]


def test_bench_serve_pipeline_stamps_and_host_share(tmp_path, monkeypatch):
    """PADDLE_TPU_BENCH_SERVE_PIPELINE rides the headline and every
    rung record; the pipelined run's serve_window host_share (the
    device-waits-for-host share, union-of-spans accounting) drops vs
    blocking; overlap_s is accounted; and `paddle compare` joins the
    two artifacts' rungs on (engine, pipeline, offered load) — nothing
    lands in only_a/only_b. Goodput direction is deliberately NOT
    asserted here: on a 1-core CI box real overlap is impossible
    (doc/serving.md); the win is pinned by
    test_ab_pipelined_overlap_acceptance's device-modeled A/B."""
    from paddle_tpu.observability import compare

    bench = _bench(monkeypatch, tmp_path)
    # one deep-overload rung with full-length decodes: every arrival is
    # effectively immediate and the window is all work — an idle-heavy
    # rung would put the same idle share in both modes' host_share and
    # drown the dispatch-bubble signal this test pins
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_RATES", "2000.0")
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_BLOCK", "1,2")
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_REQUESTS", "32")
    monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_MIXED_LEN", "0")
    kw = dict(B=2, T=4, vocab=50, dim=16, beam_size=1, max_length=8,
              dtype="float32")
    extras = {}
    for mode in ("off", "on"):
        monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_PIPELINE", mode)
        monkeypatch.setenv("PADDLE_TPU_BENCH_SERVE_DIR", str(tmp_path / mode))
        _v, e = bench.bench_serve(engine="continuous", **kw)
        obs.emit("run_end", status="completed")
        obs.flush()
        assert e["pipeline"] == mode
        assert e["decode_blocks"] == "1,2"
        assert all(r.get("pipeline") == mode for r in e["rungs"]), e["rungs"]
        extras[mode] = e

    def windows(d):
        recs = [r for rs in load_run(str(d)).values() for r in rs]
        for rec in recs:
            assert not obs.validate_record(rec), rec
        return [r for r in recs if r["kind"] == "serve_window"
                and r.get("rung", -1) >= 0]
    w_off, w_on = windows(tmp_path / "off"), windows(tmp_path / "on")
    assert all(w["pipeline"] == "off" for w in w_off)
    assert all(w["pipeline"] == "on" for w in w_on)
    assert all(w.get("overlap_s", 0.0) > 0.0 for w in w_on)
    mean = lambda ws: sum(w.get("host_share", 0.0) for w in ws) / len(ws)
    assert mean(w_on) < mean(w_off), (w_off, w_on)
    doc = compare.compare(compare.load_side(str(tmp_path / "off")),
                          compare.load_side(str(tmp_path / "on")),
                          threshold=10.0)
    strays = [k for k in list(doc.get("only_a") or []) +
              list(doc.get("only_b") or []) if str(k).startswith("serve.")]
    assert not strays, strays


# ------------------------------------------------- paddle serve e2e


SERVE_CONFIG = """
import sys
sys.path.insert(0, {demo!r})
from paddle.trainer_config_helpers import *
from seqToseq_net import gru_encoder_decoder

settings(batch_size=2, learning_rate=1e-3, learning_method=AdamOptimizer())
gru_encoder_decoder(source_dict_dim=50, target_dict_dim=50,
                    is_generating=True, word_vector_dim=16,
                    encoder_size=16, decoder_size=16, beam_size=1,
                    max_length=6)
"""


def test_paddle_serve_eof_batch_answers_everything(tmp_path):
    """Plain stdin EOF is a BATCH, not an abort: `paddle serve <
    requests.jsonl` completes every accepted request and prints its
    result line before exiting 0 — EOF must not drain-reject the queue
    the client just piped (found driving the real CLI; only a signal
    rejects)."""
    cfg = tmp_path / "serve_conf.py"
    cfg.write_text(SERVE_CONFIG.format(
        demo=os.path.join(REPO, "demo", "seqToseq")))
    reqs = "\n".join(json.dumps(
        {"id": f"b{i}", "prompt": [4 + i, 7], "max_new_tokens": 2 + i}
    ) for i in range(5))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.cli", "serve",
         f"--config={cfg}", "--use_tpu=0", "--serve_slots=2",
         "--serve_prompt_tokens=4", "--serve_decode_block=1,2"],
        input=reqs + "\n", capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr
    lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
    by_id = {l["id"]: l for l in lines}
    assert set(by_id) == {f"b{i}" for i in range(5)}, by_id
    for i in range(5):
        assert by_id[f"b{i}"]["outcome"] == "ok", by_id
        assert len(by_id[f"b{i}"]["tokens"]) == 2 + i, by_id


def test_paddle_serve_sigterm_graceful_drain(tmp_path):
    """`paddle serve` drains gracefully on SIGTERM: in-flight requests
    complete (their result lines are printed), queued/new requests are
    rejected, the exit code is 0, and run_end status=completed is the
    stream's LAST record."""
    cfg = tmp_path / "serve_conf.py"
    cfg.write_text(SERVE_CONFIG.format(
        demo=os.path.join(REPO, "demo", "seqToseq")))
    run_dir = tmp_path / "run"
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.cli", "serve",
         f"--config={cfg}", "--use_tpu=0", "--serve_slots=2",
         "--serve_prompt_tokens=4", "--serve_decode_block=1,2",
         f"--compile_cache_dir={tmp_path / 'ccache'}",
         f"--metrics_path={run_dir}"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        for i in range(3):
            proc.stdin.write(json.dumps(
                {"id": f"s{i}", "prompt": [4 + i, 7], "max_new_tokens": 4}
            ) + "\n")
        proc.stdin.flush()
        # wait for the first completed result — the engine is live and
        # mid-load — then ask for the graceful drain. All stdout reads
        # go through the SAME buffered object: readline() may buffer
        # more than one line, and a later communicate() would read the
        # raw fd and silently drop that buffer.
        first = proc.stdout.readline()
        assert first.strip(), "no result line before SIGTERM"
        proc.send_signal(signal.SIGTERM)
        # watchdog: a wedged drain must fail THIS test, not eat the
        # suite budget behind a blocking read
        import threading

        killer = threading.Timer(120.0, proc.kill)
        killer.start()
        try:
            rest = proc.stdout.read()      # until EOF at process exit
            rc = proc.wait(timeout=30)
            err = proc.stderr.read()
        finally:
            killer.cancel()
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.stdin.close()
        proc.stdout.close()
        proc.stderr.close()
    lines = [json.loads(l) for l in ([first] + rest.splitlines()) if l.strip()]
    assert rc == 0, (rc, err)
    assert "drained" in err
    by_id = {l["id"]: l for l in lines}
    assert set(by_id) == {"s0", "s1", "s2"}, by_id
    assert by_id["s0"]["outcome"] == "ok" and len(by_id["s0"]["tokens"]) == 4
    assert all(l["outcome"] in ("ok", "rejected") for l in lines)
    # telemetry: run_end status=completed is the LAST record
    recs = [r for rs in load_run(str(run_dir)).values() for r in rs]
    assert recs, "no serve telemetry written"
    for rec in recs:
        assert not obs.validate_record(rec), (rec, obs.validate_record(rec))
    assert recs[-1]["kind"] == "run_end"
    assert recs[-1]["status"] == "completed"
    wins = [r for r in recs if r["kind"] == "serve_window"]
    assert wins and wins[-1]["engine"] == "continuous"
