"""Plain reference of the Jamba hybrid decoder (``jamba``: Mamba-1
mixers, an attention mixer every ``attn_layer_period`` layers, a dense
SwiGLU feed-forward in every block, a tied head; published description:
the model's config.json, huggingface.co/ai21labs/AI21-Jamba2-3B, and
``transformers``' ``modeling_jamba.py``).

Straightforward ``jax.numpy`` in float32 with matmul precision
"highest": the selective scan ROW BY ROW (a ``lax.scan`` over the rows
of a sequence, the state ``h`` written ``[d_state, d_inner]``), full
causal attention, no kernels, no cache. It imports nothing of the
program and takes nothing the program made: its weights come from
``benchmarks.weights`` (the seed), layer by layer, through the family's
``derive_leaf``.

Layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset``, else Mamba. Block: ``x = x + mixer(rms(x;
input_layernorm))``, then ``x = x + mlp(rms(x; pre_ff_layernorm))``;
after the last block ``rms(x; final_layernorm)``; logits ``= x @
embed_tokens.weight.T``. MLP: ``down(silu(gate(x)) * up(x))``.

- attention: ``q, k, v = x W_q, x W_k, x W_v``, no bias, NO rotary or
  other positional term, causal softmax at scale ``1/sqrt(head_dim)``,
  the K/V heads shared by their groups of query heads, then ``W_o``;
- Mamba-1, per token row ``t`` (``d`` = d_inner, ``n`` = d_state, ``r`` =
  dt_rank): ``x_t, z_t = split(u_t W_in)``; ``x_t = silu(conv_t(x))``,
  the depthwise causal convolution over ``mamba_d_conv`` rows with its
  bias; ``dt_t, B_t, C_t = split(x_t W_x, [r, n, n])``; an RMS norm on
  each of the three (Jamba's addition to Mamba-1); ``dt_t =
  softplus(dt_t W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t[:,
  None] A) h_{t-1} + (dt_t x_t)[:, None] B_t[None, :]``, ``h_{-1} = 0``;
  ``y_t = h_t C_t + D x_t``; ``y_t = y_t silu(z_t)``; ``out_t = y_t
  W_out``.

Departures from the published model (stated in the configuration's
file): random weights; the derived ``A_log``, ``dt_proj.bias`` and
``conv1d_weight``; every intermediate in float32 (the published model
holds the conv's output and the time step in bfloat16 between its
kernels); the order of the layer types is the family's rule (the
catalog lists it as not given).

``serve_gaps(..., precision="int8")`` is the control: every matmul in
int8 (per-output-channel weights, per-row activations), int8 K/V, and
the state-space state rounded to bfloat16 after every row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights as W
from benchmarks.families import jamba as fam
from benchmarks.reference.llama_dense import (_attn_group, _blocks,
                                              _int8_rows, _mm, rms_norm)


def _dtype(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def _leaf(seed, name, shape, cfg):
    """A leaf as the configuration serves it (stored in its dtype, the
    derived ones derived), in float32."""
    v = W.make_leaf(np.uint32(W.leaf_salt(seed, name)), tuple(shape),
                    _dtype(cfg)).astype(jnp.float32)
    return fam.derive_leaf(name, v, cfg)


def layer_weights(seed, cfg, i):
    kind = fam.layer_kinds(cfg)[i]
    return {k: _leaf(seed, f"model.layers.{i}.{k}", shp, cfg)
            for k, shp in fam.layer_shapes(cfg, kind).items()}


# -- the two mixers and the feed-forward ------------------------------------

def mamba(u, lw, cfg, precision="float32", norms=True):
    """u [b, s, hidden] -> [b, s, hidden], the recurrence row by row from
    zero state. ``norms=False`` leaves the ``dt`` / ``B`` / ``C`` norms
    out (plain Mamba-1: what the planted fault computes)."""
    m = fam.mamba_dims(cfg)
    d, n, r, w = m["d"], m["n"], m["r"], m["w"]
    b, s, _ = u.shape
    eps = cfg["rms_norm_eps"]
    mm = functools.partial(_mm, precision=precision)
    x, z = jnp.split(mm(u, lw["mamba.in_proj.weight"]), 2, axis=-1)
    cat = jnp.concatenate([jnp.zeros((b, w - 1, d), jnp.float32), x], axis=1)
    x = jax.nn.silu(lw["mamba.conv1d_bias"] + sum(
        cat[:, i:i + s] * lw["mamba.conv1d_weight"][:, i] for i in range(w)))
    dt, B, C = jnp.split(mm(x, lw["mamba.x_proj.weight"]), [r, r + n],
                         axis=-1)
    if norms:
        dt = rms_norm(dt, lw["mamba.dt_layernorm.weight"], eps)
        B = rms_norm(B, lw["mamba.b_layernorm.weight"], eps)
        C = rms_norm(C, lw["mamba.c_layernorm.weight"], eps)
    dt = jax.nn.softplus(mm(dt, lw["mamba.dt_proj.weight"])
                         + lw["mamba.dt_proj.bias"])
    A = -jnp.exp(lw["mamba.A_log"]).T                          # [n, d]

    def row(h, t):
        x_t, dt_t, B_t, C_t = t             # [b, d], [b, d], [b, n], [b, n]
        h = (jnp.exp(dt_t[:, None, :] * A) * h
             + (dt_t * x_t)[:, None, :] * B_t[:, :, None])
        if precision != "float32":          # the control's bfloat16 state
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
        return h, jnp.sum(h * C_t[:, :, None], axis=1)

    # h is written [n, d], the published [d, n] transposed: the same
    # numbers, and a row of it fills the chip's 128 lanes where 16 state
    # indices would fill an eighth of them
    _, y = jax.lax.scan(row, jnp.zeros((b, n, d), jnp.float32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)), unroll=8)
    y = (jnp.moveaxis(y, 0, 1) + lw["mamba.D"] * x) * jax.nn.silu(z)
    return mm(y, lw["mamba.out_proj.weight"])


def attention(u, lw, cfg, precision="float32"):
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  fam.head_dim(cfg))
    b, s, _ = u.shape
    mm = functools.partial(_mm, precision=precision)
    q = mm(u, lw["self_attn.q_proj.weight"]).reshape(b, s, nq, d)
    k = mm(u, lw["self_attn.k_proj.weight"]).reshape(b, s, nkv, d)
    v = mm(u, lw["self_attn.v_proj.weight"]).reshape(b, s, nkv, d)
    if precision == "int8":     # as an int8 KV pool stores them
        k, v = _int8_rows(k), _int8_rows(v)
    qg = q.reshape(b, s, nkv, nq // nkv, d).transpose(2, 0, 1, 3, 4)
    o = jax.lax.map(lambda t: _attn_group(*t),
                    (qg, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, s, nq * d)
    return mm(o, lw["self_attn.o_proj.weight"])


def layer(x, lw, cfg, kind, precision="float32"):
    eps = cfg["rms_norm_eps"]
    mm = functools.partial(_mm, precision=precision)
    u = rms_norm(x, lw["input_layernorm.weight"], eps)
    x = x + (mamba(u, lw, cfg, precision) if kind == fam.MAMBA
             else attention(u, lw, cfg, precision))
    u = rms_norm(x, lw["pre_ff_layernorm.weight"], eps)
    ff = (jax.nn.silu(mm(u, lw["feed_forward.gate_proj.weight"]))
          * mm(u, lw["feed_forward.up_proj.weight"]))
    return x + mm(ff, lw["feed_forward.down_proj.weight"])


# -- serving: the gaps of served tokens ---------------------------------------

def _embedding(seed, cfg):
    name = "model.embed_tokens.weight"
    return _leaf(seed, name, fam.param_shapes(cfg)[name], cfg)


def _hidden_blocks(seed, cfg, seqs, precision):
    """The decoder's output (before the final norm) for sequences of
    token ids, layer by layer over blocks of sequences
    (``llama_dense._blocks``), so that neither the model nor the whole
    sample sits on the chip at once."""
    emb = _embedding(seed, cfg)
    blocks, xs = list(_blocks([len(s) for s in seqs])), []
    for width, rows, idx in blocks:
        ids = np.zeros((rows, width), np.int32)
        for r, i in enumerate(idx):
            ids[r, :len(seqs[i])] = seqs[i]   # right padding: causal,
        xs.append(jnp.take(emb, jnp.asarray(ids), axis=0))   # never seen
    del emb
    kinds = fam.layer_kinds(cfg)
    fns = {kind: jax.jit(functools.partial(layer, cfg=cfg, kind=kind,
                                           precision=precision))
           for kind in set(kinds)}
    for i, kind in enumerate(kinds):
        lw = layer_weights(seed, cfg, i)
        xs = [fns[kind](x, lw) for x in xs]
        del lw
    return blocks, xs


def _norm_and_head(seed, cfg):
    """The final norm's gain and the head ``[hidden, vocab]``: the
    embedding, transposed (``tie_word_embeddings``)."""
    name = "model.final_layernorm.weight"
    return (_leaf(seed, name, fam.param_shapes(cfg)[name], cfg),
            _embedding(seed, cfg).T)


def logits_rows(seed, cfg, seqs, first_rows, precision="float32"):
    """Reference logits for several sequences, each a list of token ids:
    for sequence i the rows from position ``first_rows[i]`` on, on the
    host."""
    blocks, xs = _hidden_blocks(seed, cfg, seqs, precision)
    norm_w, head = _norm_and_head(seed, cfg)
    out = [None] * len(seqs)
    for (_, _, idx), x in zip(blocks, xs):
        x = rms_norm(x, norm_w, cfg["rms_norm_eps"])
        for r, i in enumerate(idx):
            out[i] = np.asarray(_mm(x[r, first_rows[i]:len(seqs[i])], head,
                                    precision))
    return out


def serve_gaps(seed, cfg, served, precision="float32"):
    """``served``: list of (prompt, tokens) a greedy engine emitted.
    Returns per request the gaps ``best - logit[token]`` of its tokens
    by the float32 reference, and (for a control arm) the gaps of the
    tokens the lower precision puts first."""
    seqs = [list(p) + list(t[:-1]) for p, t in served]
    blocks, xs = _hidden_blocks(seed, cfg, seqs, "float32")
    norm_w, head = _norm_and_head(seed, cfg)
    eps = cfg["rms_norm_eps"]

    def logits(x, norm_w, head, how):
        return _mm(rms_norm(x, norm_w, eps), head, how)

    @jax.jit
    def below_best(x, norm_w, head, tokens):
        lg = logits(x, norm_w, head, "float32")
        picked = jnp.take_along_axis(lg, tokens[..., None], axis=-1)[..., 0]
        return lg.max(axis=-1) - picked

    def per_request(tokens_of_block):
        out = [None] * len(served)
        for k, ((_, _, idx), x) in enumerate(zip(blocks, xs)):
            g = np.asarray(below_best(x, norm_w, head, tokens_of_block(k)))
            for r, i in enumerate(idx):
                out[i] = g[r, len(served[i][0]) - 1:len(seqs[i])]
        return out

    def served_tokens(k):
        width, rows, idx = blocks[k]
        tok = np.zeros((rows, width), np.int32)
        for r, i in enumerate(idx):
            p, t = served[i]
            tok[r, len(p) - 1:len(p) - 1 + len(t)] = t
        return jnp.asarray(tok)

    gaps = per_request(served_tokens)
    if precision == "float32":
        return gaps, None
    _, low_xs = _hidden_blocks(seed, cfg, seqs, precision)
    first_choice = jax.jit(lambda x, norm_w, head: logits(
        x, norm_w, head, precision).argmax(axis=-1).astype(jnp.int32))
    return gaps, per_request(
        lambda k: first_choice(low_xs[k], norm_w, head))
