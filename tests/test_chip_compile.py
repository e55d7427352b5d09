"""The Pallas kernels the model DSL can select, compiled by the TPU's own
compiler for a DESCRIBED v5e (nothing attached, nothing run) at the widths
of the model that uses each one — section 2 of the on-chip-measurement
guide. Interpret mode cannot see what Mosaic refuses (unaligned slices,
lane broadcasts, VMEM); this can, at no chip time.

Every case also pins the gate: where ``supported()`` says yes the kernel
compiles, and a shape the compiler refuses is one the gate refuses too.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
# libtpu lets one process at a time in (a lock file under /tmp), because a
# chip takes one owner. Nothing here touches a chip, and test runners work
# in several processes at once: let each load its own copy
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def chip():
    """One described v5e device. The persistent compile cache is off
    around these compiles: an executable compiled for a described device
    is written to it but cannot be read back without a chip (the guide's
    section 2), so the next run would only warn and compile again."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, shapes, chip, grad):
    """Compile ``fn`` (and its backward when ``grad``) for the described
    chip; returns the optimized HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    if grad:
        diff = tuple(i for i, (_, d) in enumerate(shapes)
                     if jnp.issubdtype(d, jnp.floating))
        target = jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(F32)), argnums=diff)
    else:
        target = fn
    return jax.jit(target).lower(*args).compile().as_text()


# ------------------------------------------------------------------ cases


def _lstm(B, H, dtype, flat, T=8):
    from paddle_tpu.ops import pallas_lstm as pk

    acts = ("tanh", "sigmoid", "tanh")
    x = ((B, T * 4 * H) if flat else (T, B, 4 * H), dtype)
    shapes = [x, ((T, B), dtype), ((H, 4 * H), dtype), ((3, H), dtype)]
    fn = lambda x4, m, w, p: pk.fused_lstm(x4, m, w, p, acts, False, flat)
    return fn, shapes, pk.supported(*acts, B, H, jnp.dtype(dtype).itemsize)


def _gru(B, H, dtype, flat, T=8):
    from paddle_tpu.ops import pallas_gru as pg

    acts = ("tanh", "sigmoid")
    x = ((B, T * 3 * H) if flat else (T, B, 3 * H), dtype)
    shapes = [x, ((T, B), dtype), ((H, 3 * H), dtype)]
    fn = lambda x3, m, w: pg.fused_gru(x3, m, w, acts, False, flat)
    return fn, shapes, pg.supported(*acts, B, H, jnp.dtype(dtype).itemsize)


def _attention_gru(B, dtype, Te=32, Td=32, D=512, E=1024):
    from paddle_tpu.ops import pallas_attention_gru as pag

    shapes = [((Te, B, D), dtype), ((Te, B, E), dtype), ((Te, B, 1), dtype),
              ((Td, B, 3 * D), dtype), ((Td, B, 1), dtype), ((B, D), dtype),
              ((D, D), dtype), ((1, D), dtype), ((1, D), dtype),
              ((E, 3 * D), dtype), ((D, 3 * D), dtype)]
    fn = lambda *a: pag.fused_attention_gru(*a, ("tanh", "sigmoid"), False)
    return fn, shapes, pag.supported(B, Te, D, E, jnp.dtype(dtype).itemsize)


def _flash(T, D, dtype, B=2, H=4):
    """What `sequence_parallel.full_attention` runs on a TPU backend: the
    flash kernels under the causal rule and the lengths (demo/long_context:
    dim 64 over 4 heads; heads narrower than a lane tile, so the head-major
    form of `pallas_attention.by_column`), gated by
    ``pallas_attention.supported``."""
    from paddle_tpu.ops import pallas_attention as pa

    shapes = [((B, T, H, D), dtype)] * 3 + [((B,), jnp.int32)]
    fn = lambda q, k, v, n: pa.flash_attention(q, k, v, lengths=n, causal=True)
    return fn, shapes, pa.supported(T, D, jnp.dtype(dtype).itemsize)


def _rule_attention(kind, T=8192, H=32, Hkv=4, D=128, B=1, pairs=None):
    """The flash kernel of `ops/pallas_attention.py` under a mask rule at
    the block-diffusion cell's shapes (perfbench `sdar.train`: 32 query / 4
    key-value heads of 128, 8192 positions) or at `laguna.train`'s (8
    key-value heads under 64 query heads and a 512-wide window, or under 48
    and the causal rule), as `rule_attention` runs it inside the train
    step: the forward kernel and the backward one. ``pairs``: how many PAIRS
    of partial tiles the host must have found by query tile (forward) and
    by key tile (backward), so that the case compiles the pair steps."""
    from paddle_tpu.ops import pallas_attention as pa
    from paddle_tpu.ops.attention_mask import MaskRule, tile_walk

    rule = MaskRule(kind, 4 if kind == "block_diffusion" else 0,
                    512 if kind == "sliding_window" else 0)
    if pairs is not None:
        edge = pa.default_block(T)
        assert pairs == tuple(int(tile_walk(rule, T, edge, edge, t).counts[2::3].sum())
                              for t in (False, True))
    shapes = [((B, T, H, D), BF16), ((B, T, Hkv, D), BF16), ((B, T, Hkv, D), BF16)]
    fn = lambda q, k, v: pa.flash_attention(q, k, v, rule=rule)
    return fn, shapes, pa.supported(T, D, 2)


def _latent_attention(split, B=1, T=8192, H=32, dn=128, dr=64, dv=128):
    """The flash kernels at `kanana.train`'s shapes (perfbench: 32 heads,
    scores over 128 lanes a head + 64 rotary lanes, values of 128, the
    causal rule): ``split``, as the latent layer runs them, two score parts
    with the rotary part's ONE key head shared by all the query heads; or
    one 192-wide score operand a head (the single-operand form the split
    one was measured against)."""
    from paddle_tpu.ops import pallas_attention as pa
    from paddle_tpu.ops.attention_mask import MaskRule

    rule = MaskRule("causal")
    if split:
        shapes = [((B, T, H, dn), BF16), ((B, T, H, dr), BF16), ((B, T, H, dn), BF16),
                  ((B, T, 1, dr), BF16), ((B, T, H, dv), BF16)]
        fn = lambda qn, qr, kn, kr, v: pa.flash_attention((qn, qr), (kn, kr), v, rule=rule)
        return fn, shapes, pa.supported(T, (dn, dr), 2, dv)
    shapes = [((B, T, H, dn + dr), BF16), ((B, T, H, dn + dr), BF16), ((B, T, H, dv), BF16)]
    fn = lambda q, k, v: pa.flash_attention(q, k, v, rule=rule)
    return fn, shapes, pa.supported(T, dn + dr, 2, dv)


def _expert_ffn(N=4096, D=2048, F=768, held=16, k=8):
    """The sparse-expert layer's dispatch and grouped products
    (`ops/grouped_matmul.py`) at the cell's shapes: chunks of 8,192 rows of
    2048, 16 held experts of width 768, 8 choices a token (4,096 positions
    here, 32,768 in the cell: the loops' trip counts are data and the
    kernels' shapes a chunk's, so the fewer positions only compile faster)."""
    from paddle_tpu.ops.grouped_matmul import expert_ffn, route

    shapes = [((N, D), BF16), ((N, k), F32), ((N, k), jnp.int32),
              ((held, D, F), BF16), ((held, D, F), BF16), ((held, F, D), BF16)]
    fn = lambda x, w, chosen, wg, wu, wd: expert_ffn(
        x, w, wg, wu, wd, route(chosen, 0, held))
    return fn, shapes, True


def _head_prologue(heads, B=4, T=8192, Dh=128):
    """The grouped-query head prologue's two kernels
    (`ops/pallas_head_prologue.py`) at the block-diffusion cell's shapes: 4
    sequences of 8,192 positions, the 32 query heads or the 4 key heads of
    128, norm and turn on, bfloat16."""
    from paddle_tpu.ops import pallas_head_prologue as hp

    shapes = [((B, T, heads * Dh), BF16), ((Dh,), F32), ((T, Dh), F32), ((T, Dh), F32)]
    fn = lambda x, gain, c, s: hp._prologue(x, gain, (c, s), Dh, 1e-6, Dh ** -0.5, "compiled")
    return fn, shapes, hp.supported(T, heads * Dh, Dh, 2)


def _head_prologue_partial(heads, B=4, T=8192, Dh=128, rot=64):
    """The same kernels with a PARTIAL turn and no norm, as `laguna.train`'s
    full-attention layers run them: the first 64 of a head's 128 lanes
    turned (two rolls, three tables), the 48 query heads or the 8 key heads."""
    from paddle_tpu.ops import pallas_head_prologue as hp

    shapes = [((B, T, heads * Dh), BF16)] + [((T, Dh), F32)] * 3
    fn = lambda x, c, up, down: hp._prologue(x, None, (c, up, down), Dh, 1e-6, Dh ** -0.5,
                                             "compiled", rot)
    return fn, shapes, hp.supported(T, heads * Dh, Dh, 2)


def _head_prologue_packed(heads, B=4, T=8192, d=64):
    """The same kernels with `kanana.train`'s 32 rotary heads of 64 lanes,
    two to a lane tile (`head_prologue` tiles the turn's tables): no norm,
    the whole head turned, the scores' scale."""
    from paddle_tpu.ops import pallas_head_prologue as hp

    shapes = [((B, T, heads * d), BF16), ((T, d), F32), ((T, d), F32)]
    fn = lambda x, c, s: hp._prologue(x, None, hp._packed_tables((c, s), 128 // d), 128, 1e-6,
                                      192 ** -0.5, "compiled", d)
    return fn, shapes, hp.supported(T, heads * d, 128, 2)


def _head_gate(heads, B=4, T=8192, D=128):
    """`laguna.train`'s per-head output gate on the flash kernels' result
    where it lies, [B, T, heads*128]: `attention_gate` each way and, for the
    gate's own gradient, `attention_delta` (the backward kernel's, too)."""
    from paddle_tpu.ops import pallas_attention as pa

    shapes = [((B, T, heads * D), BF16), ((B, T, heads), F32)]
    fn = lambda x, g: pa._gate(x, g, pa.default_block(T), False)
    return fn, shapes, True


def _conv1x1(M, K, N, dtype):
    from paddle_tpu.ops import pallas_conv1x1_bn as pcb

    shapes = [((M, K), dtype), ((K, N), dtype), ((N,), dtype)]
    fn = lambda x, w, b: pcb.conv1x1_stats(x, w, b, False)[0]
    return fn, shapes, pcb.supported(M, K, N, jnp.dtype(dtype).itemsize)


CASES = {
    # flagship LSTM classifier (flagship.flagship_config at B=256, H=512)
    "lstm-bf16": lambda: _lstm(256, 512, BF16, False),
    "lstm-bf16-flat": lambda: _lstm(256, 512, BF16, True),
    "lstm-f32": lambda: _lstm(256, 512, F32, False),
    # seqToseq NMT encoder (H=512; nmt.train runs 256, 448 is the most
    # the kernel ALONE compiles at: PERF.md section 7, fault 1)
    "gru-bf16-b256": lambda: _gru(256, 512, BF16, False),
    "gru-bf16-b448": lambda: _gru(448, 512, BF16, False),
    "gru-bf16-b448-flat": lambda: _gru(448, 512, BF16, True),
    "gru-f32-b256": lambda: _gru(256, 512, F32, False),
    # seqToseq NMT decoder (D=512, E=1024)
    "attention-gru-bf16-b64": lambda: _attention_gru(64, BF16),
    "attention-gru-bf16-b256": lambda: _attention_gru(256, BF16),
    "attention-gru-bf16-b448": lambda: _attention_gru(448, BF16),
    "attention-gru-f32-b256": lambda: _attention_gru(256, F32),
    # demo/long_context (seq_len=2048; dim 64 over 4 heads, and a 64-wide head)
    "flash-t2048-d16": lambda: _flash(2048, 16, F32),
    "flash-t2048-d64": lambda: _flash(2048, 64, BF16),
    # perfbench sdar.train: block-diffusion attention and the held experts
    "rule-attention-block-diffusion-t8192": lambda: _rule_attention("block_diffusion"),
    "rule-attention-causal-t8192": lambda: _rule_attention("causal"),
    "expert-ffn-16x768": _expert_ffn,
    "head-prologue-q-32x128": lambda: _head_prologue(32),
    "head-prologue-k-4x128": lambda: _head_prologue(4),
    # perfbench laguna.train: the window and the causal rule over 8 key/value
    # heads, and the partial turn of the full-attention layers
    "rule-attention-window-64-heads-t8192": lambda: _rule_attention("sliding_window", H=64, Hkv=8),
    "rule-attention-causal-48-heads-t8192": lambda: _rule_attention("causal", H=48, Hkv=8),
    # the cells' whole shapes with the pair steps in: laguna.train's window
    # layers (a pair a row of tiles both ways), sdar.train's (a pair a noised
    # query tile, none by key tile); and the longest axis the gate admits at
    # this head, whose plan counts a pair's second score tile and the head's dq
    "rule-attention-pairs-window-4x64-heads-t8192": lambda: _rule_attention(
        "sliding_window", H=64, Hkv=8, B=4, pairs=(15, 15)),
    "rule-attention-pairs-block-diffusion-4x32-heads-t8192": lambda: _rule_attention(
        "block_diffusion", B=4, pairs=(8, 0)),
    "rule-attention-pairs-window-t17408": lambda: _rule_attention(
        "sliding_window", T=17408, H=8, Hkv=8, pairs=(33, 33)),
    # perfbench kanana.train: latent attention's score parts (128 a head + 64
    # shared from one key head) over 128-wide values, and the one-operand form
    "rule-attention-latent-split-32-heads-t8192": lambda: _latent_attention(True),
    "rule-attention-latent-192-over-128-32-heads-t8192": lambda: _latent_attention(False),
    "head-prologue-partial-q-48x128": lambda: _head_prologue_partial(48),
    "head-prologue-partial-k-8x128": lambda: _head_prologue_partial(8),
    "head-prologue-packed-q-rope-32x64": lambda: _head_prologue_packed(32),
    "head-gate-64x128": lambda: _head_gate(64),
    "head-gate-48x128": lambda: _head_gate(48),
    # a ResNet-50 1x1 at B=256: stage-1 expand, 56x56 pixels, 64 -> 256
    "conv1x1-bf16": lambda: _conv1x1(256 * 56 * 56, 64, 256, BF16),
}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_where_its_gate_says_yes(case, grad, chip):
    fn, shapes, gate = CASES[case]()
    try:
        hlo = _compile(fn, shapes, chip, grad)
    except Exception as e:  # noqa: BLE001 — whatever the compiler raises
        assert not gate, (
            f"{case}: supported() admits a shape the v5e compiler refuses: "
            f"{type(e).__name__}: {str(e)[:400]}")
        return
    assert "tpu_custom_call" in hlo, f"{case}: no Mosaic kernel in the HLO"


def test_the_flash_gate_plans_for_a_pairs_second_score_tile_and_the_heads_dq():
    """`supported()` plans 12 score-sized float32 temporaries (not the 8 of
    the unpaired kernels) beside what the backward kernel keeps of a query
    head: q and its cotangent twice over, dq's bfloat16 block twice over
    and its float32 accumulator. At a head of 128 in bfloat16 the longest
    axis it admits is 34 tiles of 512 (compiled above with its pairs in),
    and 35, which 8 temporaries or a plan without dq would admit, it
    refuses."""
    from paddle_tpu.ops import pallas_attention as pa

    plan = lambda T, tiles, dq: (4 + dq) * T * 128 * 2 + (tiles * 512 * 512 + 8 * 512 * 128) * 4
    assert pa.supported(17408, 128) and plan(17408, 12, 4) <= pa._VMEM_PLAN
    assert not pa.supported(17920, 128) and pa._VMEM_PLAN < plan(17920, 12, 4)
    assert max(plan(17920, 8, 4), plan(17920, 12, 0)) <= pa._VMEM_PLAN


# the flash kernels at each cell's whole shape (4 sequences of 8,192 positions)
CELL_ATTENTION = {
    "sdar.train-block-diffusion-32-over-4":
        CASES["rule-attention-pairs-block-diffusion-4x32-heads-t8192"],
    "laguna.train-window-64-over-8": CASES["rule-attention-pairs-window-4x64-heads-t8192"],
    "laguna.train-causal-48-over-8": lambda: _rule_attention("causal", H=48, Hkv=8, B=4),
    "kanana.train-causal-128+64-over-128": lambda: _latent_attention(True, B=4),
}


@pytest.mark.parametrize("cell", sorted(CELL_ATTENTION))
def test_the_backward_is_one_kernel_that_fits_vmem_at_each_cells_shape(cell, chip):
    """The backward of `flash_attention` at a cell's whole shape (4 x 8,192
    positions, heads of 128 or of 128 + 64 over values of 128, every
    operand but the 64-lane q_rope a column block of [B, T, heads*D]: the
    BlockSpecs' 256-byte runs are Mosaic's to accept) is ONE Mosaic call,
    `attention_bwd`, which keeps a query head's q, cotangent and dq in
    VMEM: the v5e compiler takes it (a VMEM refusal shows here, on the
    CPU), and the program holds `attention_fwd` and the small
    `attention_delta` beside it and nothing else of Mosaic's."""
    fn, shapes, gate = CELL_ATTENTION[cell]()
    assert gate
    hlo = _compile(fn, shapes, chip, grad=True)
    calls = [line.split(" = ")[0] for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(re.search(r"attention_[a-z]+", c).group() for c in calls) == [
        "attention_bwd", "attention_delta", "attention_fwd"], calls


def test_a_recomputation_block_runs_the_flash_forward_once(chip):
    """The kernel's in-step form (the cases above prove it alone): under a
    recomputation block (`graph/network.py::recompute_block`) and a
    gradient, the block keeps the kernel's named `out` and `lse`, so the
    program holds `attention_fwd` once beside `attention_bwd`; a bare
    `jax.checkpoint` holds it twice."""
    from paddle_tpu.graph.network import recompute_block
    from paddle_tpu.observability.compile_log import hlo_census

    fn, shapes, gate = _rule_attention("block_diffusion")
    assert gate
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]

    def calls(block):
        loss = lambda *a: jnp.sum(block(*a).astype(F32))
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(*args).compile()
        return hlo_census(compiled, compiled.as_text())["mosaic_calls"]

    assert calls(recompute_block(fn)) == 3      # forward, `attention_delta`, backward
    assert calls(jax.checkpoint(fn)) == 4


def _step_compiled(stem, chip, monkeypatch, inputs=("tokens", "labels"),
                   batch=4, length=8192):
    """A benchmark configuration's REAL train step (its DSL file at its own
    sizes -> GradientMachine's gradient under its recomputation blocks ->
    the updater, parameters and optimizer state donated as the trainer
    donates them) compiled for the described chip from shapes alone:
    nothing is allocated here. The kernels are steered on in the test (the
    program asks `jax.default_backend()`, which is the CPU here). Every
    input is `batch` id sequences of `length`."""
    from paddle_tpu.config import parse_config
    from paddle_tpu.graph.argument import Argument
    from paddle_tpu.graph.machine import GradientMachine, compute_dtype_of
    from paddle_tpu.optimizer.updater import Updater
    from paddle_tpu.utils import device

    monkeypatch.setattr(device, "pallas_mode", lambda: "compiled")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    conf = parse_config(os.path.join(root, "perfbench", "configs", stem + ".py"),
                        f"feed=x,feed_list=y,batch={batch}")
    gm = GradientMachine(conf.model_config, compute_dtype=compute_dtype_of(conf.opt_config))
    updater = Updater(conf.opt_config, conf.model_config)
    params = jax.eval_shape(lambda: gm.init_params(seed=1))
    state = jax.eval_shape(updater.init_state, params)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)
    ids = jax.ShapeDtypeStruct((batch, length), jnp.int32, sharding=chip)
    lens = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=chip)
    feed = {name: Argument(ids=ids, seq_lengths=lens) for name in inputs}
    grad_fn = gm.grad_fn(remat=conf.opt_config.remat, sparse=True)

    def step(params, opt_state, in_args):
        loss, grads, outputs, _ = grad_fn(params, in_args, None)
        new_params, new_state = updater(params, grads, opt_state, jnp.float32(batch))
        return new_params, new_state, loss

    return jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(state), feed).compile()


def test_the_latent_cells_real_step_fits_the_chip(chip, monkeypatch):
    """`kanana.train`'s step at its published widths (576 M parameters, 4 x
    8,192 tokens) compiles for a v5e with its `mem_total_bytes` under
    14.4 GB of the 16.9 GB the chip gives a program (14.27 GB; 15.73 while
    the flash kernels took head-major copies of their operands and the 32
    rotary heads of 64 went through XLA's float32 passes, each padded to
    whole lane tiles), with the flash kernels in (three a layer: forward,
    `attention_delta` and backward; a block keeps the forward's `out` and
    `lse`), the latent's scopes in its `op_name`s, and NO copy or transpose
    of an array of q_nope's size (4 x 8,192 x 32 x 128, however it is
    shaped) under an attention layer's scope: q_nope, k_nope, v, the
    result, its cotangent and their gradients stay where the products
    leave and read them. What is still transposed is q_rope, 32 heads of
    64 lanes."""
    from paddle_tpu.observability.compile_log import hlo_census
    from paddle_tpu.observability.memory import memory_analysis_of

    compiled = _step_compiled("kanana-2-30b-a3b-ep8", chip, monkeypatch)
    text = compiled.as_text()
    memory = memory_analysis_of(compiled)
    assert memory["mem_arg_bytes"] == pytest.approx(12 * 575955968, rel=0.001)
    assert memory["mem_total_bytes"] < 14.4e9, memory
    calls = re.findall(r"%(attention_\w+?)[.\d]* = ", text)
    assert {k: calls.count(k) for k in set(calls)} == {
        "attention_fwd": 5, "attention_delta": 5, "attention_bwd": 5}
    moved = [line.split(" = ")[0].strip() for line in text.splitlines()
             if re.search(r" (copy|transpose)\(", line) and "multi_head_attention" in line
             and any(math.prod(map(int, dims.split(","))) == 4 * 8192 * 32 * 128
                     for dims in re.findall(r" = \w+\[([\d,]+)\]", line))]
    assert not moved, moved
    assert hlo_census(compiled, text)["mosaic_calls"] > 10
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("latent_down", "latent_up"):
        assert any(f"/{scope}/" in n for n in names), scope


def test_the_decoders_real_step_stacks_no_tanh_of_its_attention(chip, monkeypatch):
    """`nmt.train`'s step (256 pairs, both sides padded to 128, widths 512)
    compiles for a v5e with its attention's scores over the 128 source
    positions remade by the decoder's backward loop: no `[128, 256, 128,
    512]` array (the tanh, T x B x S x D) is left in the program, and
    `mem_total_bytes` is under 7 GB (5.23 GB; 15.28 while the forward loop
    stacked the tanh and 1 - tanh)."""
    from paddle_tpu.observability.memory import memory_analysis_of

    compiled = _step_compiled(
        "seqtoseq-wmt14", chip, monkeypatch, batch=256, length=128,
        inputs=("source_language_word", "target_language_word",
                "target_language_next_word"))
    assert "[128,256,128,512]" not in compiled.as_text()
    memory = memory_analysis_of(compiled)
    assert memory["mem_total_bytes"] < 7e9, memory
