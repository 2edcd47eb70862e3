"""Plain reference of the openPangu-Ultra-MoE decoder
(``pangu_ultra_moe``; published description: the model's config.json,
huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B, and the
multi-head latent attention / shared-expert design it follows).

Straightforward ``jax.numpy`` in float32 with matmul precision
"highest": latent attention in its UNABSORBED form (every head's keys
and values made from the latent, full causal softmax, one head at a
time), the expert layer as a loop over the HELD experts with a dense
mask, no kernels, no cache. It imports nothing of the program and takes
nothing the program made: its weights come from ``benchmarks.weights``
(the seed), layer by layer.

Per block, ``N_*`` RMSNorm: ``a = N_post_attn(MLA(N_in(x))); x = x + a;
m = N_post_mlp(FFN(N_pre_mlp(x))); x = x + m`` (with
``assumed.post_norm_on_output`` false: ``x = N_post(x + sublayer)``).

- MLA: ``cq = N_q(u W_DQ)``; ``q = cq W_UQ`` -> heads of (nope | rope);
  ``(ckv | kr) = u W_DKV``; ``c = N_kv(ckv)``; ``kr = RoPE(kr)``, one
  key for every head; per head ``(k_nope | v) = c W_UKV,h``;
  ``s = (q_nope . k_nope + RoPE(q_rope) . kr) / sqrt(nope + rope)``,
  causal softmax, ``out = concat_h(p v_h) W_O``. RoPE is plain,
  rotate-half (``assumed.rope_interleave``: neighbouring pairs).
- FFN, dense layers: ``(silu(u W_g) * (u W_u)) W_d``.
- FFN, expert layers: ``s = sigmoid(u W_r)`` over all experts in
  float32 (``assumed.router_score_bias``: plus a bias that chooses but
  does not weigh); the ``num_experts_per_tok`` largest; ``w = scale x s
  / sum of the chosen s``; the HELD experts' part of ``sum w_e
  (silu(u G_e) * (u U_e)) D_e``; plus the shared expert of the same
  form.

Departures (stated in the configuration files): random weights; depth,
leading dense layers, experts held and vocabulary cut to this chip's
share; no multi-token prediction module.

``serve_gaps(..., precision="int8")`` is the control: every matmul in
int8 (per-output-channel weights, per-row activations; the router stays
float32) and the cached row ``(c | kr)`` rounded to int8 per row, as an
int8 latent pool would store it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights as W
from benchmarks.families import pangu_moe as fam
from benchmarks.reference.llama_dense import (HI, _blocks, _int8_rows, _mm,
                                              rms_norm)


def _dtype(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def _leaf(seed, name, shape, cfg):
    """A leaf as the configuration serves it (stored in its dtype), in
    float32."""
    return W.make_leaf(np.uint32(W.leaf_salt(seed, name)), tuple(shape),
                       _dtype(cfg)).astype(jnp.float32)


def layer_weights(seed, cfg, i):
    kind = fam.layer_kinds(cfg)[i]
    return {k: _leaf(seed, f"model.layers.{i}.{k}", shp, cfg)
            for k, shp in fam.layer_shapes(cfg, kind).items()}


def rope(x, theta, interleave=False):
    """x [b, s, heads, d], positions 0..s-1."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _cached(x, precision):
    """What the next precision down keeps of a cached row."""
    if precision == "int8":
        return _int8_rows(x)
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def attention(u, lw, cfg, precision="float32"):
    h, dn, dr, dv, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                        cfg["kv_lora_rank"])
    b, s, _ = u.shape
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    inter = cfg["assumed"]["rope_interleave"]
    mm = functools.partial(_mm, precision=precision)
    cq = rms_norm(mm(u, lw["self_attn.q_a_proj.weight"]),
                  lw["self_attn.q_a_layernorm.weight"], eps)
    q = mm(cq, lw["self_attn.q_b_proj.weight"]).reshape(b, s, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], theta, inter)],
                        axis=-1)
    ckv, kr = jnp.split(mm(u, lw["self_attn.kv_a_proj_with_mqa.weight"]),
                        [r], axis=-1)
    c = rms_norm(ckv, lw["self_attn.kv_a_layernorm.weight"], eps)
    kr = rope(kr[:, :, None, :], theta, inter)[:, :, 0]
    row = _cached(jnp.concatenate([c, kr], axis=-1), precision)
    c, kr = row[..., :r], row[..., r:]
    w_ukv = lw["self_attn.kv_b_proj.weight"].reshape(r, h, dn + dv)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def head(t):                # one head: its keys and values, its rows
        qh, w = t               # [b, s, dn + dr], [r, dn + dv]
        kv = mm(c, w)
        k = jnp.concatenate([kv[..., :dn], kr], axis=-1)
        sc = jnp.einsum("bqd,bkd->bqk", qh, k, precision=HI) \
            / np.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, kv[..., dn:], precision=HI)

    o = jax.lax.map(head, (q.transpose(2, 0, 1, 3),
                           w_ukv.transpose(1, 0, 2)))       # [h, b, s, dv]
    return mm(o.transpose(1, 2, 0, 3).reshape(b, s, h * dv),
              lw["self_attn.o_proj.weight"])


def route(u, lw, cfg):
    """-> (chosen expert ids [.., k], their weights [.., k]); float32
    whatever the arm: the router is kept in float32."""
    s = jax.nn.sigmoid(jnp.matmul(u, lw["mlp.gate.weight"], precision=HI))
    chooses = s + lw.get("mlp.e_score_correction_bias", 0.0)
    _, idx = jax.lax.top_k(chooses, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * cfg["routed_scaling_factor"]


def _gated(u, g, up, down, mm):
    return mm(jax.nn.silu(mm(u, g)) * mm(u, up), down)


def moe(u, lw, cfg, precision="float32", held=None):
    """The part of the layer that the experts ``held`` = (first, count)
    give (``cfg["experts_held"]`` where None; ``lw``'s expert leaves are
    those experts'), plus the shared expert."""
    first, count = held or cfg["experts_held"]
    mm = functools.partial(_mm, precision=precision)
    idx, w = route(u, lw, cfg)
    comb = jnp.sum(jax.nn.one_hot(idx - first, count, dtype=jnp.float32)
                   * w[..., None], axis=-2)                   # [b, s, count]

    def one(acc, t):            # every row through one held expert
        g, up, down, c = t
        return acc + c[..., None] * _gated(u, g, up, down, mm), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(u),
        (lw["mlp.experts.w_gate"], lw["mlp.experts.w_in"],
         lw["mlp.experts.w_out"], jnp.moveaxis(comb, -1, 0)))
    return routed + _gated(u, lw["mlp.shared_gate.weight"],
                           lw["mlp.shared_up.weight"],
                           lw["mlp.shared_down.weight"], mm)


def layer(x, lw, cfg, kind, precision="float32"):
    eps = cfg["rms_norm_eps"]
    on_output = cfg["assumed"]["post_norm_on_output"]
    mm = functools.partial(_mm, precision=precision)

    def residual(x, out, name):
        w = lw[f"{name}.weight"]
        return (x + rms_norm(out, w, eps) if on_output
                else rms_norm(x + out, w, eps))

    a = attention(rms_norm(x, lw["input_layernorm.weight"], eps), lw, cfg,
                  precision)
    x = residual(x, a, "post_attention_layernorm")
    u = rms_norm(x, lw["pre_mlp_layernorm.weight"], eps)
    m = (moe(u, lw, cfg, precision) if kind == fam.MOE else
         _gated(u, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"],
                lw["mlp.down_proj.weight"], mm))
    return residual(x, m, "post_mlp_layernorm")


# -- serving: the gaps of served tokens ---------------------------------------

def _hidden_blocks(seed, cfg, seqs, precision):
    """The decoder's output (before the final norm) for sequences of
    token ids, layer by layer over blocks of sequences
    (``llama_dense._blocks``), so that neither the model nor the whole
    sample sits on the chip at once."""
    shapes = fam.param_shapes(cfg)
    emb = _leaf(seed, "model.embed_tokens.weight",
                shapes["model.embed_tokens.weight"], cfg)
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
    shapes = fam.param_shapes(cfg)
    return (_leaf(seed, "model.norm.weight", shapes["model.norm.weight"],
                  cfg),
            _leaf(seed, "lm_head.weight", shapes["lm_head.weight"], cfg))


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
