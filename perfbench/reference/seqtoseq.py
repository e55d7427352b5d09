"""Plain reference of the seqToseq attention GRU encoder-decoder.

Straightforward `jax.numpy`, float32, matmuls at `highest` precision, no
kernels, no cache, no batching tricks. It imports nothing from
`paddle_tpu` and takes nothing the program made: the weights come from
`init_params(sizes, seed)` here, and the harness hands the SAME arrays to
the program.

Equations, as the reference framework's layers compute them (SURVEY.md;
the reference tree was not mounted in this session, so each is as this
repo's docstrings cite it):

- `embedding_layer`: a table lookup.
- `simple_gru` (`trainer_config_helpers/networks.py`): a `mixed_layer`
  with one `full_matrix_projection` and NO bias giving the 3H
  x-projection, then `GatedRecurrentLayer`: with x3 = [x_u, x_r, x_c],
  W = [W_u W_r | W_c] of shape [H, 3H] and bias b of 3H:
      u, r = sigmoid(x_u + h W_u + b_u), sigmoid(x_r + h W_r + b_r)
      c    = tanh(x_c + (r * h) W_c + b_c)
      h'   = u * h + (1 - u) * c            (GruCompute: u keeps the old)
  The backward encoder runs the same cell from each sentence's last real
  token to its first.
- `gru_encoder_decoder` (`demo/seqToseq/seqToseq_net.py`): encoded =
  concat(forward, backward); encoded_proj = encoded W_p (no bias);
  decoder_boot = tanh(backward[first position] W_b) (no bias).
- `simple_attention:943`: e_j = v . tanh(s W_a + encoded_proj_j),
  a = softmax over the sentence's real positions, context = sum a_j
  encoded_j.
- decoder step: x3 = context W_c3 + emb(word) W_w3 (no bias), the GRU
  cell above with its own W and bias, then softmax(h W_o + b_o).
- `classification_cost`: -log p[next word], summed over a pair's real
  target positions; the trainer's loss is the mean over the batch's
  pairs.

Departures: none in the mathematics. Padding: positions past a sentence's
length are masked (state held, cost 0, attention weight 0), which is what
the reference's no-padding sequence packing computes.

`mode` selects the arithmetic of every matrix product: "highest" is the
reference; "fp8" is the control of lower precision (`mm`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

def param_shapes(sizes):
    """Reference name -> shape. A leading 1 marks a bias."""
    e, h, d = (sizes["word_vector_dim"], sizes["encoder_size"],
               sizes["decoder_size"])
    vs, vt = sizes["source_dict_dim"], sizes["target_dict_dim"]
    return {
        "src_emb": (vs, e), "trg_emb": (vt, e),
        "enc_fwd_x": (e, 3 * h), "enc_fwd_w": (h, 3 * h), "enc_fwd_b": (1, 3 * h),
        "enc_bwd_x": (e, 3 * h), "enc_bwd_w": (h, 3 * h), "enc_bwd_b": (1, 3 * h),
        "enc_proj": (2 * h, d), "boot": (h, d),
        "att_w": (d, d), "att_v": (d, 1),
        "dec_ctx": (2 * h, 3 * d), "dec_word": (e, 3 * d),
        "dec_w": (d, 3 * d), "dec_b": (1, 3 * d),
        "out_w": (d, vt), "out_b": (1, vt),
    }


BIAS_STD = 0.02


@functools.partial(jax.jit, static_argnums=(0,))
def _init(shape_items, key):
    out = {}
    for i, (name, shape) in enumerate(shape_items):
        k = jax.random.fold_in(key, i)
        std = BIAS_STD if shape[0] == 1 else shape[0] ** -0.5
        out[name] = std * jax.random.normal(k, shape, jnp.float32)
    return out


def init_params(sizes, seed):
    """All weights in one jitted call on the device, float32 (the type the
    trainer keeps its master weights in and the server is given)."""
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    return _init(tuple(sorted(param_shapes(sizes).items())), key)


def _as_fp8(x):
    """x rounded to float8_e4m3fn as an fp8 recipe does it: scaled so that
    the tensor's largest magnitude lands on the type's (448), rounded, and
    scaled back. The rounding is passed straight through by the backward
    pass, which so sees the rounded values and full-precision gradients."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def mm(a, b, mode):
    """The model's every matrix product. "fp8" is the control: both inputs
    rounded to float8_e4m3fn (per-tensor scale), exact products, float32
    accumulation: the gentlest step below bfloat16, with nothing lost to the
    type's range and nothing rounded on the way back."""
    if mode == "fp8":
        a, b = _as_fp8(a), _as_fp8(b)
    elif mode != "highest":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def gru_cell(x3, h, w, b, mode):
    n = h.shape[-1]
    g = x3[..., : 2 * n] + mm(h, w[:, : 2 * n], mode) + b[0, : 2 * n]
    u, r = jnp.split(jax.nn.sigmoid(g), 2, axis=-1)
    c = jnp.tanh(x3[..., 2 * n:] + mm(r * h, w[:, 2 * n:], mode) + b[0, 2 * n:])
    return u * h + (1.0 - u) * c


def _gru_layer(x3, mask, w, b, mode, reverse):
    """x3 [B, T, 3H], mask [B, T] -> outputs [B, T, H]; the state is held
    over masked positions, so a reversed run starts at the last real
    token with a zero state."""
    def cell(h, xs):
        x_t, m_t = xs
        h2 = gru_cell(x_t, h, w, b, mode)
        h = jnp.where(m_t[:, None], h2, h)
        return h, h
    h0 = jnp.zeros((x3.shape[0], w.shape[0]), jnp.float32)
    xs = (jnp.swapaxes(x3, 0, 1), jnp.swapaxes(mask, 0, 1))
    _, hs = jax.lax.scan(cell, h0, xs, reverse=reverse)
    return jnp.swapaxes(hs, 0, 1)


def encode(p, src, src_len, mode):
    mask = jnp.arange(src.shape[1])[None, :] < src_len[:, None]
    emb = p["src_emb"][src]
    fwd = _gru_layer(mm(emb, p["enc_fwd_x"], mode), mask,
                     p["enc_fwd_w"], p["enc_fwd_b"], mode, False)
    bwd = _gru_layer(mm(emb, p["enc_bwd_x"], mode), mask,
                     p["enc_bwd_w"], p["enc_bwd_b"], mode, True)
    enc = jnp.concatenate([fwd, bwd], axis=-1) * mask[..., None]
    enc_proj = mm(enc, p["enc_proj"], mode)
    boot = jnp.tanh(mm(bwd[:, 0], p["boot"], mode))
    return enc, enc_proj, boot, mask


def decode_states(p, enc, enc_proj, boot, mask, words, mode):
    """Teacher-forced decoder: `words` [B, T] are the words fed at each
    step (<s> first). Returns the GRU states [B, T, D]."""
    word_x = mm(p["trg_emb"][words], p["dec_word"], mode)

    def step(h, x_t):
        s = mm(h, p["att_w"], mode)
        e = mm(jnp.tanh(s[:, None, :] + enc_proj), p["att_v"], mode)[..., 0]
        a = jax.nn.softmax(jnp.where(mask, e, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bt,btd->bd", a, enc,
                         precision=jax.lax.Precision.HIGHEST)
        h = gru_cell(mm(ctx, p["dec_ctx"], mode) + x_t, h,
                     p["dec_w"], p["dec_b"], mode)
        return h, h
    _, hs = jax.lax.scan(step, boot, jnp.swapaxes(word_x, 0, 1))
    return jnp.swapaxes(hs, 0, 1)


def logits(p, src, src_len, words, mode="highest"):
    """[B, T, V] scores before the softmax at every fed position."""
    enc, enc_proj, boot, mask = encode(p, src, src_len, mode)
    hs = decode_states(p, enc, enc_proj, boot, mask, words, mode)
    return mm(hs, p["out_w"], mode) + p["out_b"][0]


def cost_sum(p, batch, mode="highest"):
    """Sum over the rows' pairs of the cost summed over each pair's real
    target positions (divide by the batch's pair count for the loss)."""
    z = logits(p, batch["src"], batch["src_len"], batch["trg_in"], mode)
    logp = jax.nn.log_softmax(z, axis=-1)
    picked = jnp.take_along_axis(logp, batch["trg_next"][..., None], axis=-1)[..., 0]
    tmask = jnp.arange(z.shape[1])[None, :] < batch["trg_len"][:, None]
    return -jnp.sum(jnp.where(tmask, picked, 0.0))


@functools.partial(jax.jit, static_argnames=("mode",))
def _block_grad(p, block, inv_b, mode):
    return jax.value_and_grad(
        lambda q: cost_sum(q, block, mode) * inv_b)(p)


BLOCK_POSITIONS = 4096      # rows x T of one block: about 0.5 GB of scores


def loss_and_grad(p, batch, mode="highest"):
    """The batch's loss (mean over pairs) and its gradient, computed in
    blocks of rows so that a whole batch's [rows, T, V] scores never sit
    in memory at once."""
    n, t = batch["trg_in"].shape
    cap = max(1, BLOCK_POSITIONS // t)
    rows = max(r for r in range(1, min(cap, n) + 1) if n % r == 0)
    inv_b = jnp.float32(1.0 / n)
    loss, grads = None, None
    for i in range(0, n, rows):
        block = {k: v[i:i + rows] for k, v in batch.items()}
        l, g = _block_grad(p, block, inv_b, mode)
        loss = l if loss is None else loss + l
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    return loss, grads


def to_batch(arrays):
    """The traffic generator's named arrays -> this reference's batch."""
    return {"src": arrays["source_language_word"],
            "src_len": arrays["source_language_word.len"],
            "trg_in": arrays["target_language_word"],
            "trg_next": arrays["target_language_next_word"],
            "trg_len": arrays["target_language_word.len"]}
