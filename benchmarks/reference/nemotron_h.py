"""Plain reference of the Nemotron-H hybrid decoder (``nemotron_h``:
Mamba-2, grouped-query attention and LatentMoE blocks by a pattern
string; published description: the model's config.json and model card,
huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16).

Straightforward ``jax.numpy`` in float32 with matmul precision
"highest": the state-space recurrence ROW BY ROW (a ``lax.scan``; not
the chunked form the program uses), full causal attention, the expert
layer as a loop over the HELD experts with a dense mask, no kernels, no
cache. It imports nothing of the program and takes nothing the program
made: its weights come from ``benchmarks.weights`` (the seed), layer by
layer, through the family's ``derive_leaf``.

Block ``i`` of kind ``pattern[i]``: ``x = x + mixer_i(rmsnorm(x))``;
after the last block the final norm and the untied head.

- ``M``: ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC))`` over the
  last ``conv_kernel`` rows; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T``, ``y_t = S_t C_t + D x_t``; ``y = rmsnorm_grouped(y silu(z))``;
  ``out = y W_out``.
- ``*``: GQA, causal, scale ``1/sqrt(head_dim)``, rotary only where
  ``assumed.attention_rope`` says so.
- ``E``: ``s = sigmoid(u W_r)`` over all experts in float32; the
  ``num_experts_per_tok`` largest of ``s + bias``; ``w = scale x s /
  sum of the chosen s``; ``l = u W_down``; ``f_e(l) = relu(l W1_e)^2
  W2_e``; the HELD experts' part of ``sum w_e f_e(l)``, through
  ``W_up``; plus the shared expert ``relu(u V1)^2 V2``.

Departures (stated in the configuration files): random weights; depth,
experts held and vocabulary cut to this chip's share; the derived
``A_log`` / ``dt_bias``.

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
from benchmarks.families import nemotron_h as fam
from benchmarks.reference.llama_dense import (HI, _blocks, _int8_rows, _mm,
                                              rms_norm, rope)


def _dtype(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def _leaf(seed, name, shape, cfg):
    """A leaf as the configuration serves it (stored in its dtype, the
    derived ones derived), in float32."""
    v = W.make_leaf(np.uint32(W.leaf_salt(seed, name)), tuple(shape),
                    _dtype(cfg)).astype(jnp.float32)
    return fam.derive_leaf(name, v, cfg)


def layer_weights(seed, cfg, i):
    kind = cfg["hybrid_override_pattern"][i]
    return {k: _leaf(seed, f"model.layers.{i}.{k}", shp, cfg)
            for k, shp in fam.layer_shapes(cfg, kind).items()}


def relu2(x):
    return jnp.square(jax.nn.relu(x))


# -- the three mixers ----------------------------------------------------------

def mamba(u, lw, cfg, precision="float32", state=None):
    """u [b, s, hidden] -> [b, s, hidden], the recurrence row by row from
    ``state`` (conv rows [b, w - 1, c], S [b, h, p, n]; zeros where None).
    Returns the output and the state after the last row."""
    d = fam.mamba_dims(cfg)
    h, p, g, n = d["h"], d["p"], d["g"], d["n"]
    b, s, _ = u.shape
    w = cfg["conv_kernel"]
    mm = functools.partial(_mm, precision=precision)
    z, xbc, dt = jnp.split(mm(u, lw["mixer.in_proj.weight"]),
                           [d["d_inner"], d["d_inner"] + d["conv_dim"]],
                           axis=-1)
    rows = (jnp.zeros((b, w - 1, d["conv_dim"]), jnp.float32)
            if state is None else state[0])
    cat = jnp.concatenate([rows, xbc], axis=1)
    conv = lw["mixer.conv1d_bias"] + sum(
        cat[:, i:i + s] * lw["mixer.conv1d_weight"][:, i] for i in range(w))
    x, B, C = jnp.split(jax.nn.silu(conv), [h * p, h * p + g * n], axis=-1)
    x = x.reshape(b, s, h, p)
    B = jnp.repeat(B.reshape(b, s, g, n), h // g, axis=2)     # [b, s, h, n]
    C = jnp.repeat(C.reshape(b, s, g, n), h // g, axis=2)
    dt = jax.nn.softplus(dt + lw["mixer.dt_bias"])            # [b, s, h]
    A = -jnp.exp(lw["mixer.A_log"])
    S0 = (jnp.zeros((b, h, p, n), jnp.float32) if state is None
          else state[1])

    def row(S, t):
        x_t, B_t, C_t, dt_t = t
        S = (jnp.exp(dt_t * A)[:, :, None, None] * S
             + (dt_t[:, :, None] * x_t)[..., None] * B_t[:, :, None, :])
        if precision != "float32":      # the control's bfloat16 state
            S = S.astype(jnp.bfloat16).astype(jnp.float32)
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=HI)

    S, y = jax.lax.scan(row, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, B, C, dt)))
    y = jnp.moveaxis(y, 0, 1) + x * lw["mixer.D"][None, None, :, None]
    y = y.reshape(b, s, h * p) * jax.nn.silu(z)
    yg = y.reshape(b, s, g, h * p // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                            + cfg["layer_norm_epsilon"])
    y = yg.reshape(b, s, h * p) * lw["mixer.norm_weight"]
    return mm(y, lw["mixer.out_proj.weight"]), (cat[:, s:], S)


def attention(u, lw, cfg, precision="float32"):
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    b, s, _ = u.shape
    mm = functools.partial(_mm, precision=precision)
    q = mm(u, lw["mixer.q_proj.weight"]).reshape(b, s, nq, d)
    k = mm(u, lw["mixer.k_proj.weight"]).reshape(b, s, nkv, d)
    v = mm(u, lw["mixer.v_proj.weight"]).reshape(b, s, nkv, d)
    if cfg["assumed"]["attention_rope"]:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    if precision == "int8":     # as an int8 KV pool stores them
        k, v = _int8_rows(k), _int8_rows(v)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def group(t):               # one kv head with its query heads
        qg, kg, vg = t          # [b, s, r, d], [b, s, d], [b, s, d]
        sc = jnp.einsum("bqrd,bkd->brqk", qg, kg, precision=HI) / np.sqrt(d)
        pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("brqk,bkd->bqrd", pr, vg, precision=HI)

    qg = q.reshape(b, s, nkv, nq // nkv, d).transpose(2, 0, 1, 3, 4)
    o = jax.lax.map(group, (qg, k.transpose(2, 0, 1, 3),
                            v.transpose(2, 0, 1, 3)))
    o = o.transpose(1, 2, 0, 3, 4).reshape(b, s, nq * d)
    return mm(o, lw["mixer.o_proj.weight"])


def route(u, lw, cfg):
    """-> (chosen expert ids [.., k], their weights [.., k]); float32
    whatever the arm: the router is kept in float32."""
    s = jax.nn.sigmoid(jnp.matmul(u, lw["mixer.gate.weight"], precision=HI))
    _, idx = jax.lax.top_k(s + lw["mixer.e_score_correction_bias"],
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w * cfg["routed_scaling_factor"]


def moe(u, lw, cfg, precision="float32", held=None):
    """The part of the layer that the experts ``held`` = (first, count)
    give (``cfg["experts_held"]`` where None; ``lw``'s expert leaves are
    those experts'), plus the shared expert."""
    first, count = held or cfg["experts_held"]
    mm = functools.partial(_mm, precision=precision)
    idx, w = route(u, lw, cfg)
    comb = jnp.sum(jax.nn.one_hot(idx - first, count, dtype=jnp.float32)
                   * w[..., None], axis=-2)                   # [b, s, count]
    lat = mm(u, lw["mixer.fc1_latent_proj.weight"])

    def one(acc, t):            # every row through one held expert
        w1, w2, c = t
        return acc + c[..., None] * mm(relu2(mm(lat, w1)), w2), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(lat),
        (lw["mixer.experts.w_in"], lw["mixer.experts.w_out"],
         jnp.moveaxis(comb, -1, 0)))
    shared = mm(relu2(mm(u, lw["mixer.shared_up.weight"])),
                lw["mixer.shared_down.weight"])
    return mm(routed, lw["mixer.fc2_latent_proj.weight"]) + shared


def layer(x, lw, cfg, kind, precision="float32"):
    u = rms_norm(x, lw["norm.weight"], cfg["layer_norm_epsilon"])
    if kind == fam.MAMBA:
        return x + mamba(u, lw, cfg, precision)[0]
    if kind == fam.ATTENTION:
        return x + attention(u, lw, cfg, precision)
    return x + moe(u, lw, cfg, precision)


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
    fns = {kind: jax.jit(functools.partial(layer, cfg=cfg, kind=kind,
                                           precision=precision))
           for kind in set(cfg["hybrid_override_pattern"])}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        lw = layer_weights(seed, cfg, i)
        xs = [fns[kind](x, lw) for x in xs]
        del lw
    return blocks, xs


def _norm_and_head(seed, cfg):
    shapes = fam.param_shapes(cfg)
    return (_leaf(seed, "model.norm_f.weight",
                  shapes["model.norm_f.weight"], cfg),
            _leaf(seed, "lm_head.weight", shapes["lm_head.weight"], cfg))


def logits_rows(seed, cfg, seqs, first_rows, precision="float32"):
    """Reference logits for several sequences, each a list of token ids:
    for sequence i the rows from position ``first_rows[i]`` on, on the
    host."""
    blocks, xs = _hidden_blocks(seed, cfg, seqs, precision)
    norm_w, head = _norm_and_head(seed, cfg)
    out = [None] * len(seqs)
    for (_, _, idx), x in zip(blocks, xs):
        x = rms_norm(x, norm_w, cfg["layer_norm_epsilon"])
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
    eps = cfg["layer_norm_epsilon"]

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
