"""Plain reference of the dense GQA decoder (Llama/Mistral layout).

Straightforward ``jax.numpy`` in float32 with matmul precision "highest":
no kernels, no cache, no batching tricks. It imports nothing of the
program and takes nothing the program made: its weights come from
``benchmarks.weights`` (the seed), layer by layer, so that a 16-layer
model at published widths never sits on the chip whole.

Departures from the published description (both stated in the
configuration files): weights are random; the depth is cut.

- ``serve_gaps``: logits of whole sequences (no cache), layer by layer,
  and for each served token the gap between the reference's best logit
  and the served token's logit. ``precision="int8"`` is the control: the
  same forward with every matmul in int8 (per-output-channel weights,
  per-row activations) and per-row int8 K/V, as an int8 pool stores them.
- ``train_reference``: the first steps of AdamW training, layer by layer
  (forward keeps the layer inputs, backward walks the layers in reverse
  and applies AdamW to a layer as soon as its gradient exists), so that
  only parameters and moments are resident: 12 bytes a parameter.
  ``precision="fp8"`` is the control: every matmul operand rounded to
  float8_e4m3 (``"bfloat16"``: the control of a float32 configuration). ``half_batch=True`` is the planted fault "half of the
  batch left out, the mean taken over the rest".
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights as W

HI = jax.lax.Precision.HIGHEST
LAYER_LEAVES = ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
                "self_attn.v_proj.weight", "self_attn.o_proj.weight",
                "mlp.gate_proj.weight", "mlp.up_proj.weight",
                "mlp.down_proj.weight", "input_layernorm.weight",
                "post_attention_layernorm.weight")


def _shapes(cfg):
    from benchmarks.families.llama_dense import param_shapes
    return param_shapes(cfg)


def _dtype(cfg):
    return jnp.dtype(cfg["torch_dtype"])


# -- lower-precision arms (the controls) -----------------------------------

def _int8_cols(w):
    """Per-output-channel absmax int8, dequantised ([in, out] layout)."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(w / s).clip(-127, 127) * s


def _int8_rows(x):
    """Per-row (last axis) absmax int8, dequantised: K/V as an int8 pool
    stores them."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).clip(-127, 127) * s


def _ste(x, q):
    """The rounded value forward, the identity backward."""
    return x + jax.lax.stop_gradient(q - x)


def _fp8(x):
    """Round to float8_e4m3 with a per-tensor scale."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return _ste(x, (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s)


def _mm(a, b, precision):
    """``a @ b`` with both operands first rounded to ``precision``:
    int8 is per-row for the activations and per-output-channel for the
    weights, fp8 per tensor, bfloat16 plain; float32 rounds nothing."""
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision == "int8":
        a, b = _ste(a, _int8_rows(a)), _ste(b, _int8_cols(b))
    elif precision == "bfloat16":
        a, b = (_ste(t, t.astype(jnp.bfloat16).astype(jnp.float32))
                for t in (a, b))
    elif precision != "float32":
        raise ValueError(f"no arm for precision {precision!r}")
    return jnp.matmul(a, b, precision=HI)


# -- the layer ---------------------------------------------------------------

def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope(x, theta):
    """x: [b, s, heads, d], positions 0..s-1; rotate-half convention."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attn_group(q, k, v):
    """One kv head with its group of query heads, causal.
    q: [b, s, g, d]; k, v: [b, s, d]."""
    s, d = q.shape[1], q.shape[-1]
    sc = jnp.einsum("bqgd,bkd->bgqk", q, k, precision=HI) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bgqk,bkd->bqgd", p, v, precision=HI)


def layer(x, lw, cfg, precision="float32"):
    """One decoder layer. x: [b, s, h] float32; lw: the layer's leaves
    (float32, [in, out])."""
    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    b, s, _ = x.shape
    mm = functools.partial(_mm, precision=precision)
    h = rms_norm(x, lw["input_layernorm.weight"], cfg["rms_norm_eps"])
    q = mm(h, lw["self_attn.q_proj.weight"]).reshape(b, s, nq, d)
    k = mm(h, lw["self_attn.k_proj.weight"]).reshape(b, s, nkv, d)
    v = mm(h, lw["self_attn.v_proj.weight"]).reshape(b, s, nkv, d)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    if precision == "int8":     # as an int8 KV pool stores them
        k, v = _int8_rows(k), _int8_rows(v)
    qg = q.reshape(b, s, nkv, nq // nkv, d).transpose(2, 0, 1, 3, 4)
    # one kv group at a time, recomputed in the backward pass: the
    # [heads, s, s] scores of a 4096-token row never exist at once
    og = jax.lax.map(lambda t: jax.checkpoint(_attn_group)(*t),
                     (qg, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    o = og.transpose(1, 2, 0, 3, 4).reshape(b, s, nq * d)
    x = x + mm(o, lw["self_attn.o_proj.weight"])
    h = rms_norm(x, lw["post_attention_layernorm.weight"],
                 cfg["rms_norm_eps"])
    ff = (jax.nn.silu(mm(h, lw["mlp.gate_proj.weight"]))
          * mm(h, lw["mlp.up_proj.weight"]))
    return x + mm(ff, lw["mlp.down_proj.weight"])


def _leaf(seed, name, shape, dtype):
    """A leaf as the configuration serves it (dtype), in float32."""
    return W.make_leaf(np.uint32(W.leaf_salt(seed, name)), tuple(shape),
                       dtype).astype(jnp.float32)


# -- serving: the gaps of served tokens ---------------------------------------

BLOCK_TOKENS = 8192


def _blocks(lengths):
    """Sequences grouped into blocks of about ``BLOCK_TOKENS`` padded
    token rows: a sequence goes to the smallest power of two (from 128)
    that holds it, a block has ``BLOCK_TOKENS // width`` rows. Few shapes
    (one compile each), little padding. Yields (width, rows, [indices])."""
    by_width: dict[int, list[int]] = {}
    for i, n in enumerate(lengths):
        width = 128
        while width < n:
            width *= 2
        by_width.setdefault(width, []).append(i)
    for width in sorted(by_width):
        rows = max(1, BLOCK_TOKENS // width)
        idx = by_width[width]
        for j in range(0, len(idx), rows):
            yield width, rows, idx[j:j + rows]


def _hidden_blocks(seed, cfg, seqs, precision):
    """The decoder's output (before the final norm) for sequences of
    token ids, layer by layer over blocks of sequences, so that neither
    the model nor the whole sample sits on the chip at once. Returns the
    blocks (``_blocks``) and one [rows, width, hidden] array for each."""
    shapes, dt = _shapes(cfg), _dtype(cfg)
    emb = _leaf(seed, "model.embed_tokens.weight",
                shapes["model.embed_tokens.weight"], dt)
    blocks, xs = list(_blocks([len(s) for s in seqs])), []
    for width, rows, idx in blocks:
        ids = np.zeros((rows, width), np.int32)
        for r, i in enumerate(idx):
            ids[r, :len(seqs[i])] = seqs[i]   # right padding: causal,
        xs.append(jnp.take(emb, jnp.asarray(ids), axis=0))   # never seen
    del emb

    @jax.jit
    def layer_weights(salts):
        return {k: W.make_leaf(salts[j],
                               tuple(shapes[f"model.layers.0.{k}"]),
                               dt).astype(jnp.float32)
                for j, k in enumerate(LAYER_LEAVES)}

    one_layer = jax.jit(lambda x, lw: layer(x, lw, cfg, precision))
    for i in range(cfg["num_hidden_layers"]):
        lw = layer_weights(np.asarray(
            [W.leaf_salt(seed, f"model.layers.{i}.{k}")
             for k in LAYER_LEAVES], np.uint32))
        xs = [one_layer(x, lw) for x in xs]
    return blocks, xs


def _norm_and_head(seed, cfg):
    shapes, dt = _shapes(cfg), _dtype(cfg)
    norm_w = _leaf(seed, "model.norm.weight", shapes["model.norm.weight"], dt)
    if cfg["tie_word_embeddings"]:
        return norm_w, _leaf(seed, "model.embed_tokens.weight",
                             shapes["model.embed_tokens.weight"], dt).T
    return norm_w, _leaf(seed, "lm_head.weight", shapes["lm_head.weight"], dt)


def logits_rows(seed, cfg, seqs, first_rows, precision="float32"):
    """Reference logits for several sequences, each a list of token ids.
    Returns for sequence i the rows from position ``first_rows[i]`` on:
    [len_i - first_rows[i], V], on the host."""
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
    tokens the lower precision puts first. A block's logits (1 GB at
    8192 rows of Mistral's vocabulary) live only inside one jitted call."""
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
        """tokens_of_block(k) -> [rows, width] token ids; the gaps of
        each request's served positions."""
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


# -- training: the first steps, layer by layer --------------------------------

ADAMW = {"lr": 1e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
         "weight_decay": 0.01}


def _adamw(p, g, m, v, t, hp):
    p = p * (1 - hp["lr"] * hp["weight_decay"])
    m = hp["beta1"] * m + (1 - hp["beta1"]) * g
    v = hp["beta2"] * v + (1 - hp["beta2"]) * g * g
    mhat = m / (1 - hp["beta1"] ** t)
    vhat = v / (1 - hp["beta2"] ** t)
    return p - hp["lr"] * mhat / (jnp.sqrt(vhat) + hp["eps"]), m, v


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def train_reference(seed, cfg, batches, hp=ADAMW, precision="float32",
                    half_batch=False):
    """One AdamW step on each batch of ``batches`` (each [b, s] token
    ids; labels = ids, shifted causal LM loss, mean over positions), from
    the seed's weights and zero moments. Returns
    ``{"loss": [per step], "grad_norm": {leaf: norm at step 1},
    "delta_norm": {leaf: |p_after - p_0|}}``."""
    shapes, dt, L = _shapes(cfg), _dtype(cfg), cfg["num_hidden_layers"]
    batches = [jnp.asarray(x, jnp.int32) for x in batches]
    b, s = batches[0].shape
    eps = cfg["rms_norm_eps"]
    names = sorted(shapes)
    P = {k: _leaf(seed, k, shapes[k], dt) for k in names}
    M = {k: jnp.zeros(shapes[k], jnp.float32) for k in names}
    V = {k: jnp.zeros(shapes[k], jnp.float32) for k in names}
    valid = np.ones((b, s - 1), np.float32)
    if half_batch:
        valid[:, (s - 1) // 2:] = 0.0
    valid = jnp.asarray(valid)

    def lkeys(i):
        return {k: f"model.layers.{i}.{k}" for k in LAYER_LEAVES}

    layer_fn = jax.jit(lambda x, lw: layer(x, lw, cfg, precision))

    @jax.jit
    def layer_bwd(x, lw, dy):
        _, vjp = jax.vjp(lambda x, lw: layer(x, lw, cfg, precision), x, lw)
        return vjp(dy)

    @jax.jit
    def head_loss(x, norm_w, head_w, ids):
        def f(x, norm_w, head_w):
            h = rms_norm(x, norm_w, eps)
            logits = _mm(h, head_w, precision)[:, :-1]
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]
            return jnp.sum(nll * valid) / jnp.sum(valid)
        return jax.value_and_grad(f, argnums=(0, 1, 2))(x, norm_w, head_w)

    upd = jax.jit(lambda p, g, m, v, t: _adamw(p, g, m, v, t, hp),
                  donate_argnums=(0, 2, 3))
    out = {"loss": [], "grad_norm": {}, "delta_norm": {}}
    tied = cfg["tie_word_embeddings"]
    for t, ids in enumerate(batches, start=1):
        def apply(k, g):
            if t == 1:
                out["grad_norm"][k] = float(_norm(g))
            P[k], M[k], V[k] = upd(P[k], g, M[k], V[k], jnp.float32(t))
        xs = [jnp.take(P["model.embed_tokens.weight"], ids, axis=0)]
        for i in range(L):
            xs.append(layer_fn(xs[-1], {k: P[n] for k, n in lkeys(i).items()}))
        head_w = (P["model.embed_tokens.weight"].T if tied
                  else P["lm_head.weight"])
        loss, (dx, dnorm, dhead) = head_loss(xs[-1], P["model.norm.weight"],
                                             head_w, ids)
        out["loss"].append(float(loss))
        g_embed = jnp.zeros(shapes["model.embed_tokens.weight"], jnp.float32)
        if tied:
            g_embed = g_embed + dhead.T
        else:
            apply("lm_head.weight", dhead)
        del dhead
        apply("model.norm.weight", dnorm)
        for i in reversed(range(L)):
            dx, dlw = layer_bwd(xs[i], {k: P[n] for k, n in lkeys(i).items()},
                                dx)
            xs.pop()
            for k, n in lkeys(i).items():
                apply(n, dlw[k])
            del dlw
        g_embed = g_embed.at[ids.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))
        apply("model.embed_tokens.weight", g_embed)
        del g_embed, dx, xs
    for k in names:
        out["delta_norm"][k] = float(_norm(P[k] - _leaf(seed, k, shapes[k], dt)))
    return out
