"""The production decode backend: donated slot state on device, one
jitted launch per engine iteration — dispatched and collected as two
halves so the device never waits for the host.

Device state is a single pytree of fixed-shape ``[B, ...]`` buffers for
``B = --serve_slots`` concurrent sequences — the captured static-link
conditioning (seqToseq: encoder projection/values per slot), the decoder
memory carries (the GRU hidden the fused attention-GRU path steps), the
previous token, per-slot step counts, done flags and token budgets.
Both launch fns take the state with ``donate_argnums``, so every
iteration updates it in place (no per-step HBM churn), and both are
routed through the PR-7 :class:`CompileRegistry`:

- launch group ``serve_prefill`` — ONE ``[B, T]`` signature: the full
  graph forward in gen-capture mode (graph/decode_step.py) over a
  padded admission batch, scattered into the named slots (sentinel
  indices drop, so partial admissions reuse the same signature). In
  pipelined mode the admission launch is dispatch-only — the PR-12
  ``block_until_ready`` is gone, so admitting never stalls an in-flight
  decode; its device time surfaces inside the next decode collect span.
- launch group ``serve_decode`` — ONE ``[B, ...]`` signature for the
  WHOLE decode-block ladder: the block size ``u`` is a traced scalar
  bound on the device ``fori_loop`` (token/live buffers are sized to
  the ladder's top rung), so every rung shares one compiled executable
  and recompiles stay 0 across the ladder by construction — stronger
  than one pre-warmed signature per rung, which would show up as
  ``recompiles>0`` group churn in the compile telemetry.
- launch group ``serve_verify`` (PR 20, ``--serve_spec_tokens``) — the
  speculative draft-and-verify launch: ONE ``[K, B]`` signature for the
  whole speculation ladder (draft length ``k`` is a traced scalar, the
  draft buffer is sized to the ladder's top rung, same discipline as
  ``serve_decode``). Each step scores the next position with the real
  model; a slot keeps advancing while its drafted token matches the
  model's own greedy argmax, and the first mismatching step's token is
  the model's CORRECTION — it commits too, riding free. Every emitted
  token is therefore the model's own greedy output: exact parity with
  plain decode, unconditionally. Slots whose draft misses at once (or
  that proposed nothing) advance exactly one plain step.

Reduced-precision slot state (PR 20, ``--serve_slot_dtype=bf16``):
float slot buffers (captured statics + GRU carries) are STORED in
bfloat16 — halving per-slot HBM so ``--serve_slots`` doubles at fixed
footprint — while every step still COMPUTES in f32: statics upcast once
per launch outside the fori_loop, carries upcast before and downcast
after EVERY micro-step inside it. Rounding once per micro-step (not
once per launch) keeps the token stream identical across decode-block
rungs, so the cross-rung golden tests still hold under bf16.

``dispatch()`` enqueues the decode launch and immediately starts
``copy_to_host_async`` on its token/live/finished outputs — the PR-5
snapshot discipline: every transfer is on the wire before the first
``collect()`` blocks. ``collect()`` gathers the oldest in-flight
launch; exec time is attributed THERE, as the union of dispatch→done
spans (overlapping spans must not double-count device seconds), and a
launch fault also surfaces there — exactly where the engine's
cohort-error path expects it.

Evicted-but-unreplaced slots need no device call: a finished (or
abandoned) row's flag freezes it, an abandoned live row self-terminates
at its bounded budget, and the next admission overwrites the slot
wholesale.
"""

from __future__ import annotations

import collections
from typing import Any, List, Optional, Sequence, Union

import numpy as np

from paddle_tpu.serving.backend import (
    DraftBatch,
    StepOut,
    parse_decode_blocks,
    parse_slot_dtype,
    parse_spec_tokens,
)
from paddle_tpu.utils import concurrency as cc


class UnsupportedModelError(RuntimeError):
    """The generation graph cannot be slot-decoded (see plan_of gates);
    the static path (`SequenceGenerator`, PR-8 driver) still works."""


class JaxDecodeBackend:
    GROUP_DECODE = "serve_decode"
    GROUP_PREFILL = "serve_prefill"
    GROUP_VERIFY = "serve_verify"

    def __init__(self, machine, params, slots: int, prompt_tokens: int,
                 max_length: Optional[int] = None,
                 decode_block: Union[int, str, Sequence[int]] = 1,
                 registry=None, feed_name: Optional[str] = None,
                 pipeline: bool = True, fused_step: bool = False,
                 spec_tokens: Union[int, str, Sequence[int], None] = None,
                 slot_dtype: str = "f32"):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.graph.decode_step import (
            capture_prefill, make_greedy_step, plan_fused_step, plan_of,
            plan_slot_dtype,
        )

        self._jax, self._jnp = jax, jnp
        plan, reason = plan_of(machine)
        if plan is None:
            raise UnsupportedModelError(reason)
        self._plan = plan
        self._machine = machine
        self.params = params
        self.slots = int(slots)
        self.prompt_tokens = int(prompt_tokens)
        self.max_length = min(int(max_length or plan.max_length),
                              plan.max_length)
        self.decode_blocks = parse_decode_blocks(decode_block)
        self.max_block = self.decode_blocks[-1]
        self.spec_blocks = parse_spec_tokens(spec_tokens)
        self.max_spec = self.spec_blocks[-1] if self.spec_blocks else 0
        self.slot_dtype = parse_slot_dtype(slot_dtype)
        slot_plan, why = plan_slot_dtype(self.slot_dtype)
        if slot_plan is None:
            raise UnsupportedModelError(why)
        self._store_dtype = (jnp.dtype(slot_plan["store_dtype"])
                             if slot_plan["store_dtype"] else None)
        self.parity_tol = float(slot_plan["parity_tol"])
        self.pipeline = bool(pipeline)
        self._registry = registry
        # exec attribution gate: warmup flips it on; callers measuring
        # calibration passes may toggle it off so those launches stay
        # out of the serve roofline (the static leg's serving_now rule)
        self.serving = False
        self._warmed = False
        names = list(machine.network.input_layer_names)
        if feed_name is None:
            if len(names) != 1:
                raise UnsupportedModelError(
                    f"model has {len(names)} input layers {names} — pass "
                    "feed_name to choose the prompt sequence input"
                )
            feed_name = names[0]
        self._feed_name = feed_name
        self._capture = capture_prefill
        fused_plan = None
        if fused_step:
            fused_plan, why = plan_fused_step(machine, plan,
                                              slot_dtype=self.slot_dtype)
            if fused_plan is None:
                raise UnsupportedModelError(
                    f"--serve_fused_step: {why} (the unfused per-step "
                    "decoder still serves this model)"
                )
        self.fused_step = fused_plan is not None
        self._step = make_greedy_step(machine, plan, fused_plan=fused_plan)
        self._prefill_jit = jax.jit(self._prefill_write, donate_argnums=(1,))
        self._decode_jit = jax.jit(self._decode, donate_argnums=(1,))
        self._verify_jit = (jax.jit(self._verify, donate_argnums=(1,))
                            if self.max_spec else None)
        self._state = self._fresh_state()
        # dispatched-but-uncollected decode launches: (device arrays
        # with host copies in flight, block, dispatch wall time)
        self._inflight: collections.deque = collections.deque()
        # union-of-spans anchor: exec seconds must not double-count
        # overlapping dispatch->done spans (doc/serving.md
        # "Pipelined decode")
        self._exec_anchor = cc.perf_counter()

    # ------------------------------------------------------- jitted fns

    def _feed(self, ids, lens):
        from paddle_tpu.graph import make_seq

        return {self._feed_name: make_seq(None, lens, ids=ids)}

    def _prefill_write(self, params, state, ids, lens, slot_idx, budgets):
        """Admission launch: full-graph capture forward over the padded
        [B, T] admission batch, scattered into the slot rows named by
        ``slot_idx`` (sentinel ``B`` rows drop — one signature for every
        admission size)."""
        jnp = self._jnp
        statics, boots = self._capture(
            self._machine, self._plan, params, self._feed(ids, lens)
        )

        def scatter(dst, src):
            return dst.at[slot_idx].set(src.astype(dst.dtype), mode="drop")

        new_statics = {
            name: {f: scatter(state["statics"][name][f], statics[name][f])
                   for f in state["statics"][name]}
            for name in state["statics"]
        }
        new_carries = tuple(
            scatter(old, boot) for old, boot in zip(state["carries"], boots)
        )
        return {
            "statics": new_statics,
            "carries": new_carries,
            "prev_tok": state["prev_tok"].at[slot_idx].set(
                self._plan.bos, mode="drop"),
            "finished": state["finished"].at[slot_idx].set(False, mode="drop"),
            "steps": state["steps"].at[slot_idx].set(0, mode="drop"),
            "budget": state["budget"].at[slot_idx].set(
                budgets.astype(jnp.int32), mode="drop"),
        }

    # --------------------------------------- reduced-precision slot state
    # Under --serve_slot_dtype=bf16 the slot buffers are STORED in bf16
    # but every step COMPUTES in f32: statics upcast once per launch
    # (outside the fori_loop), carries upcast before / downcast after
    # every micro-step inside it — the per-micro-step rounding point
    # keeps token streams identical across decode-block rungs. Under
    # f32 all three helpers are identity (same jaxpr as PR 12).

    def _statics_compute(self, statics):
        if self._store_dtype is None:
            return statics
        jax, jnp = self._jax, self._jnp
        up = lambda x: (x.astype(jnp.float32)
                        if x.dtype == self._store_dtype else x)
        return jax.tree_util.tree_map(up, statics)

    def _carries_compute(self, carries):
        if self._store_dtype is None:
            return carries
        jnp = self._jnp
        return tuple(c.astype(jnp.float32)
                     if c.dtype == self._store_dtype else c for c in carries)

    def _carries_store(self, like, carries):
        if self._store_dtype is None:
            return carries
        return tuple(c.astype(o.dtype) if c.dtype != o.dtype else c
                     for o, c in zip(like, carries))

    def _decode(self, params, state, u):
        """One iteration: ``u`` greedy micro-steps over all slots,
        EOS/budget termination on device. ``u`` is a TRACED scalar: the
        ladder's rungs all run through this one compiled executable
        (buffers sized to the top rung; rows past ``u`` stay dead)."""
        jax, jnp = self._jax, self._jnp
        um, B = self.max_block, self.slots
        budget = state["budget"]
        statics = self._statics_compute(state["statics"])

        def body(i, acc):
            carries, prev, fin, steps, toks, lives = acc
            live = ~fin
            cf = self._carries_compute(carries)
            cf, tok, fin = self._step(params, statics, cf, prev, fin)
            carries = self._carries_store(carries, cf)
            steps = steps + live.astype(jnp.int32)
            fin = fin | (steps >= budget)
            return (carries, tok, fin, steps,
                    toks.at[i].set(tok), lives.at[i].set(live))

        init = (state["carries"], state["prev_tok"], state["finished"],
                state["steps"], jnp.zeros((um, B), jnp.int32),
                jnp.zeros((um, B), bool))
        carries, prev, fin, steps, toks, lives = jax.lax.fori_loop(
            0, jnp.minimum(u, um), body, init)
        new_state = dict(state, carries=carries, prev_tok=prev,
                         finished=fin, steps=steps)
        return new_state, toks, lives, fin

    def _verify(self, params, state, draft, dlen, k):
        """The speculative verify launch: up to ``k`` greedy micro-steps
        per slot, where a slot stays live only while its drafted token
        keeps matching the model's own argmax (the first mismatching
        step emits the model's corrected token, then the slot freezes
        for the rest of the launch). ``k`` is a TRACED scalar bound like
        ``_decode``'s ``u`` — the whole speculation ladder shares one
        compiled executable (draft buffer sized to the top rung).

        ``draft [K, B]`` int32, ``dlen [B]`` int32 (0 = no proposal: the
        slot takes exactly one plain greedy step). Every emitted token
        is the model's own greedy output — exact parity with plain
        decode. ``prev_tok`` must end as the last token each slot truly
        COMMITTED, so it is tracked separately from the step feed (a
        frozen row's eos emission must not pollute it)."""
        jax, jnp = self._jax, self._jnp
        km, B = self.max_spec, self.slots
        budget = state["budget"]
        statics = self._statics_compute(state["statics"])

        def body(i, acc):
            (carries, prev, committed, fin, steps, accepting,
             toks, lives) = acc
            # a slot is dead for this launch once finished OR once its
            # draft diverged (the correction already committed)
            dead = fin | ~accepting
            live = ~dead
            cf = self._carries_compute(carries)
            cf, tok, _sf = self._step(params, statics, cf, prev, dead)
            carries = self._carries_store(carries, cf)
            steps = steps + live.astype(jnp.int32)
            # real termination comes only from live rows: eos emission
            # or the budget bound (frozen rows emit eos score-free)
            fin = fin | (live & (tok == self._plan.eos)) | (steps >= budget)
            committed = jnp.where(live, tok, committed)
            accepting = live & (i < dlen) & (tok == draft[i])
            return (carries, tok, committed, fin, steps, accepting,
                    toks.at[i].set(tok), lives.at[i].set(live))

        init = (state["carries"], state["prev_tok"], state["prev_tok"],
                state["finished"], state["steps"],
                jnp.ones((B,), bool),
                jnp.zeros((km, B), jnp.int32), jnp.zeros((km, B), bool))
        (carries, _prev, committed, fin, steps, _acc, toks,
         lives) = jax.lax.fori_loop(
            0, jnp.minimum(jnp.maximum(k, 1), km), body, init)
        new_state = dict(state, carries=carries, prev_tok=committed,
                         finished=fin, steps=steps)
        return new_state, toks, lives, fin

    # ------------------------------------------------------- fresh state

    def _fresh_state(self):
        """Zeroed slot buffers, every slot finished (frozen). Shapes come
        from eval_shape of the capture — no compile, no launch. Float
        buffers land in the slot storage dtype (bf16 halves them; the
        prefill scatter's ``astype(dst.dtype)`` downcasts admissions)."""
        jax, jnp = self._jax, self._jnp
        B, T = self.slots, self.prompt_tokens
        ids = jnp.zeros((B, T), jnp.int32)
        lens = jnp.ones((B,), jnp.int32)
        statics_sd, boots_sd = jax.eval_shape(
            lambda p, i, l: self._capture(self._machine, self._plan, p,
                                          self._feed(i, l)),
            self.params, ids, lens,
        )
        store = self._store_dtype

        def zeros(sd):
            dt = sd.dtype
            if store is not None and jnp.issubdtype(dt, jnp.floating):
                dt = store
            return jnp.zeros(sd.shape, dt)

        return {
            "statics": jax.tree_util.tree_map(zeros, statics_sd),
            "carries": tuple(zeros(sd) for sd in boots_sd),
            "prev_tok": jnp.full((B,), self._plan.bos, jnp.int32),
            "finished": jnp.ones((B,), bool),
            "steps": jnp.zeros((B,), jnp.int32),
            "budget": jnp.zeros((B,), jnp.int32),
        }

    # ------------------------------------------------------------- seam

    def warmup(self) -> None:
        """Pay both compiles before serving: a no-slot prefill (all
        sentinel indices) and one decode launch PER LADDER RUNG over the
        all-finished state — zero slot effects. The block bound is a
        traced scalar, so the rung launches all hit the one compiled
        ``serve_decode`` signature: compile records land with
        ``recompiles=0`` and serving never recompiles, whatever rung
        the adaptive policy picks. Idempotent: a second call (bench
        warms the backend itself before ``Engine.start()`` re-runs it,
        possibly with ``serving`` already flipped on) is a no-op — the
        rung launches must never land in the serve roofline as real
        exec."""
        if self._warmed:
            self.serving = True
            return
        self.serving = False
        B, T = self.slots, self.prompt_tokens
        self._admit_call(
            np.zeros((B, T), np.int32), np.ones((B,), np.int32),
            np.full((B,), B, np.int32), np.zeros((B,), np.int32),
        )
        for u in self.decode_blocks:
            self.step(block=u)
        # the speculation ladder warms through the SAME one serve_verify
        # signature (traced k bound): every rung launches once over the
        # all-finished state — zero slot effects, recompiles=0 after
        for kk in self.spec_blocks:
            self.step(draft={0: [0] * kk})
        if self._registry is not None:
            # warmup launches never reach note_exec (serving is off), so
            # the registry's pending compile-cost deduction would zero
            # the FIRST real launch's exec time instead — discard it
            self._registry.drop_pending(self.GROUP_PREFILL, self._sig_prefill())
            self._registry.drop_pending(self.GROUP_DECODE, self._sig_decode())
            if self.spec_blocks:
                self._registry.drop_pending(self.GROUP_VERIFY,
                                            self._sig_verify())
        self._warmed = True
        self.serving = True

    def reset(self) -> None:
        self._state = self._fresh_state()
        self._inflight.clear()
        # a post-fault epoch must not union its first collect span
        # against the dead epoch's anchor
        self._exec_anchor = cc.perf_counter()

    def reload(self, params: Any) -> None:
        """Hot weight swap at an iteration boundary (the engine's
        ``_apply_reload_locked`` is the only caller). Params are the
        NON-donated first argument of both launch fns — dispatched
        launches already captured the old reference, so this reference
        replacement cannot tear them; same shapes/dtypes hit the same
        jit cache, so the swap costs no recompile."""
        self.params = params

    def _sig_prefill(self):
        return (self.slots, self.prompt_tokens)

    def _sig_decode(self):
        return (self.slots, self.prompt_tokens, self.max_block)

    def _sig_verify(self):
        return (self.slots, self.prompt_tokens, self.max_spec)

    def slot_state_bytes(self) -> int:
        """Stored decode-state bytes per slot (captured statics, GRU
        carries, the scalar rows) — the weights-free numerator behind
        the ``slot_bytes`` bench stamp. Cross-checked against
        ``memory_analysis()`` argument bytes in tests: halving this is
        what lets ``--serve_slots`` double at fixed footprint."""
        leaves = self._jax.tree_util.tree_leaves(self._state)
        total = sum(int(l.size) * int(l.dtype.itemsize) for l in leaves)
        return total // self.slots

    def admit(self, slot_ids: Sequence[int], requests: Sequence[Any],
              budgets: Sequence[int]) -> None:
        B, T = self.slots, self.prompt_tokens
        ids = np.zeros((B, T), np.int32)
        lens = np.ones((B,), np.int32)
        idx = np.full((B,), B, np.int32)      # sentinel: row writes nothing
        budg = np.zeros((B,), np.int32)
        for j, (slot, req) in enumerate(zip(slot_ids, requests)):
            p = np.asarray(list(req.prompt or ()), np.int32)[:T]
            if p.size:
                ids[j, : p.size] = p
            lens[j] = max(int(p.size), 1)
            idx[j] = int(slot)
            budg[j] = min(int(budgets[j]), self.max_length)
        self._admit_call(ids, lens, idx, budg)

    def _admit_call(self, ids, lens, idx, budg) -> None:
        jnp = self._jnp
        t0 = cc.perf_counter()
        args = (self.params, self._state, jnp.asarray(ids),
                jnp.asarray(lens), jnp.asarray(idx), jnp.asarray(budg))
        key = self._sig_prefill()
        if self._registry is not None:
            self._state = self._registry.call(
                self.GROUP_PREFILL, key, self._prefill_jit, *args)
        else:
            self._state = self._prefill_jit(*args)
        if not self.pipeline:
            # the PR-12 serial path: admission waits for the prefill, so
            # its measured span IS device time. Pipelined mode never
            # syncs here — the admission must not stall an in-flight
            # decode; the prefill's device time surfaces inside the next
            # decode collect span instead (doc/serving.md)
            self._jax.block_until_ready(self._state["steps"])
        if self._registry is not None and self.serving:
            self._registry.note_exec(self.GROUP_PREFILL, key,
                                     cc.perf_counter() - t0)

    def dispatch(self, block: Optional[int] = None,
                 draft: Optional[DraftBatch] = None) -> None:
        """Enqueue one decode launch and start the device->host copies
        of its outputs — no waiting. Every output's copy is on the wire
        before anyone collects (the PR-5 all-dispatch-then-collect
        snapshot discipline). With ``draft`` (slot -> proposed tokens)
        the launch is the ``serve_verify`` draft-and-verify step instead
        of a plain decode block."""
        jnp = self._jnp
        t0 = cc.perf_counter()
        if draft:
            if not self.max_spec:
                raise RuntimeError(
                    "draft dispatch on a backend with no speculation "
                    "ladder (spec_tokens unset)")
            km, B = self.max_spec, self.slots
            d = np.zeros((km, B), np.int32)
            dl = np.zeros((B,), np.int32)
            for b, toks in draft.items():
                t = [int(x) for x in toks][:km]
                if t:
                    dl[int(b)] = len(t)
                    d[:len(t), int(b)] = t
            k = max(int(dl.max()), 1)
            group, key, fn = self.GROUP_VERIFY, self._sig_verify(), \
                self._verify_jit
            args = (self.params, self._state, jnp.asarray(d),
                    jnp.asarray(dl), jnp.asarray(k, jnp.int32))
            u = k
        else:
            u = int(block) if block else self.max_block
            group, key, fn = self.GROUP_DECODE, self._sig_decode(), \
                self._decode_jit
            args = (self.params, self._state, jnp.asarray(u, jnp.int32))
        if self._registry is not None:
            out = self._registry.call(group, key, fn, *args)
        else:
            out = fn(*args)
        self._state, toks, lives, fin = out
        for arr in (toks, lives, fin):
            try:
                arr.copy_to_host_async()
            except AttributeError:  # non-PJRT array stand-ins (tests)
                break
        self._inflight.append((toks, lives, fin, u, t0, group, key))

    @property
    def inflight(self) -> int:
        return len(self._inflight)

    def collect(self) -> StepOut:
        """Gather the oldest in-flight launch. The np.asarray readbacks
        are the one sanctioned device sync of the serve loop: the
        emitted tokens ARE the scheduler's input (EOS eviction, TTFT
        stamping), and exec/TTFT attribution happens at THIS boundary —
        the only honest place under overlap."""
        if not self._inflight:
            # a scheduler bug, not a device fault: fail loudly with the
            # state instead of an opaque IndexError from popleft
            raise RuntimeError(
                "serve_decode collect() with no launch in flight "
                "(dispatch/collect pairing broken)"
            )
        toks, lives, fin, u, t_disp, group, key = self._inflight.popleft()
        t_rb0 = cc.perf_counter()
        toks_np = np.asarray(toks)
        lives_np = np.asarray(lives)
        fin_np = np.asarray(fin)
        t_done = cc.perf_counter()
        # device→host readback cost of THIS collect (the np.asarray
        # syncs above) — the engine turns it into an engine.readback
        # span for traced requests; a duration, not a timestamp, so
        # the perf_counter vs monotonic timebase mismatch cannot leak
        self.last_readback_s = t_done - t_rb0
        if self._registry is not None and self.serving:
            # union of dispatch->done spans: launch N+1 was dispatched
            # while N ran, so anchoring at max(dispatch, previous done)
            # keeps summed exec seconds <= wall seconds
            span = max(t_done - max(t_disp, self._exec_anchor), 0.0)
            self._registry.note_exec(group, key, span, batches=u)
        self._exec_anchor = max(self._exec_anchor, t_done)
        return StepOut(tokens=toks_np, live=lives_np, finished=fin_np)

    def step(self, block: Optional[int] = None,
             draft: Optional[DraftBatch] = None) -> StepOut:
        self.dispatch(block=block, draft=draft)
        return self.collect()
