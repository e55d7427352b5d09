"""Flash-attention pallas kernel vs the XLA reference (interpret mode).

The CPU-stub pattern (SURVEY.md §4): kernels run in pallas interpret mode
on CPU, asserting numerical equality with the XLA full_attention path —
forward and gradients, causal and masked variants.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_attention import flash_attention, supported
from paddle_tpu.parallel.sequence_parallel import full_attention

B, T, H, D = 2, 256, 2, 32


def _qkv(seed=0, D=D):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    return mk(), mk(), mk()


# how the kernels address a head (`pallas_attention.by_column`): a head of 32
# lanes out of a head-major [B, H, T, D] copy, a head of 128 as a column block
# of the [B, T, H*D] array the caller holds
LAYOUTS = pytest.mark.parametrize("D", [32, 128], ids=["head-major-32", "column-block-128"])


def test_supported_predicate():
    assert supported(256, 64)
    assert not supported(100, 64)      # T not divisible by blocks
    assert not supported(256, 512)     # head dim too large


def _gate_before_the_fused_backward(T, widths, itemsize, value_dim, block):
    """The gate as it was while dQ had a kernel of its own: a head and its
    cotangent twice over beside the tiles' temporaries, under 40 MiB."""
    held = sum(-(-d // 128) * 128 for d in widths + (value_dim,))
    return (2 * T * held * itemsize + 12 * block * block * 4 + 4 * block * held * 4
            <= 40 * 1024 * 1024)


# a cell's heads: scores' widths, values' width (bfloat16 in all three)
CELL_HEADS = {"sdar-and-laguna-128": ((128,), 128), "kanana-128+64-over-128": ((128, 64), 128)}


@pytest.mark.parametrize("heads", sorted(CELL_HEADS))
@pytest.mark.parametrize("T", [128, 256, 512, 1024, 2048, 4096, 8192])
def test_the_gate_keeps_its_answers_up_to_the_cells_length(T, heads):
    """The plan now counts the query head's dQ (its output block twice over
    and its float32 accumulator): at every power of two up to the cells'
    8,192 positions the old gate's answer, True, stands."""
    widths, value_dim = CELL_HEADS[heads]
    assert _gate_before_the_fused_backward(T, widths, 2, value_dim, min(T, 512))
    assert supported(T, widths, 2, value_dim)


@pytest.mark.parametrize("T,widths,itemsize", [
    (26624, (128,), 2),          # the old gate's longest axis at a head of 128
    (16384, (128, 64), 2),       # twice the latent cell's length
    (8192, (128, 64), 4),        # the latent cell's heads in float32
])
def test_the_gate_refuses_where_the_heads_dq_no_longer_fits(T, widths, itemsize):
    assert _gate_before_the_fused_backward(T, widths, itemsize, 128, 512)
    assert not supported(T, widths, itemsize, 128)


@LAYOUTS
@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal, D):
    q, k, v = _qkv(D=D)
    lengths = jnp.asarray([T, T - 77], jnp.int32)
    ref = full_attention(q, k, v, lengths=lengths, causal=causal)
    out = flash_attention(q, k, v, lengths=lengths, causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@LAYOUTS
@pytest.mark.parametrize("block", [None, 64], ids=["one-tile", "4x4-tiles"])
@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_xla(causal, block, D):
    """dq, dk and dv of the one backward kernel against the XLA path: a
    head of one tile, and of 4 x 4 tiles of 64, where a head's dq is summed
    over its key tiles in VMEM and the second sequence's end (126) cuts the
    second tile, so that whole key tiles past it add nothing."""
    q, k, v = _qkv(1, D)
    lengths = jnp.asarray([T, T - 130], jnp.int32)

    def loss_ref(q, k, v):
        o = full_attention(q, k, v, lengths=lengths, causal=causal)
        # mask padded rows out of the loss: their flash output is 0 but the
        # XLA path produces garbage values there (both are masked by
        # downstream layers in real models)
        m = (jnp.arange(T)[None, :] < lengths[:, None]).astype(o.dtype)
        return jnp.sum((o * m[..., None, None]) ** 2)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, lengths=lengths, causal=causal, interpret=True,
                            block=block)
        m = (jnp.arange(T)[None, :] < lengths[:, None]).astype(o.dtype)
        return jnp.sum((o * m[..., None, None]) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_full_lengths_default():
    q, k, v = _qkv(2)
    ref = full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
