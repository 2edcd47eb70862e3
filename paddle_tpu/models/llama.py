"""Llama model family — the flagship LLM (parity: PaddleNLP llama +
test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py, the
model the reference's hybrid-parallel stack is exercised with).

TPU-native design decisions:
- weights carry PartitionSpec axes at creation (mp = tensor parallel,
  fsdp = ZeRO-style) — GSPMD inserts the collectives the reference codes
  in fleet/layers/mpu/mp_layers.py (Column/Row/VocabParallelLinear).
- attention routes through nn.functional.scaled_dot_product_attention →
  Pallas flash kernel on TPU for long sequences (stored-LSE contract).
- rotary embeddings precomputed as a buffer; GQA via num_key_value_heads.
- everything is jit-traceable with static shapes; the KV cache for decode
  is a fixed-size buffer updated with dynamic_update_slice.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..core.dtypes import scoped_dtype_init
from ..nn.module import Layer, Parameter

__all__ = ["LlamaConfig", "LlamaForCausalLM", "LlamaModel", "LlamaDecoderLayer",
           "llama_tiny", "llama_3_8b", "llama_2_7b"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    recompute: bool = False  # remat each decoder layer (fleet recompute parity)
    # remat granularity: "full" re-runs the whole layer in backward;
    # "dots" saves matmul outputs and recomputes only elementwise chains
    # (jax dots_with_no_batch_dims_saveable) — less recompute FLOPs for a
    # modest activation-memory increase
    recompute_policy: str = "full"
    # remat every k-th layer only (parity: fleet recompute_interval) —
    # k=2 halves recompute FLOPs for ~2x boundary activation memory
    recompute_interval: int = 1
    dtype: str = "float32"
    # parallel axes (None disables the annotation; degrees of 1 are no-ops)
    mp_axis: str | None = "mp"
    fsdp_axis: str | None = "fsdp"
    # pipeline / sequence parallelism (consumed by LlamaForCausalLMPipe;
    # sep_axis also switches LlamaAttention to ring attention when tracing
    # inside a manual-sep shard_map region)
    pp_axis: str | None = None
    sep_axis: str | None = None

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def _rope_cache(config: LlamaConfig):
    dim = config.head_dim
    inv_freq = 1.0 / (config.rope_theta ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    t = jnp.arange(config.max_position_embeddings, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [S, dim/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def _mp_psum(x, axis):
    """One explicit allreduce after a row-parallel matmul (o_proj /
    down_proj) when tracing inside a manual-mp shard_map region — the
    serving engine's TP step programs (serving/parallel.py). In the
    hint-based GSPMD path (training, generate) the region is inactive and
    GSPMD inserts the same collective from the weight specs."""
    if axis is not None:
        from ..distributed.fleet.mp_layers import current_manual_mp
        if current_manual_mp() == axis:
            return jax.lax.psum(x, axis)
    return x


def _vocab_parallel_embed(w, input_ids, axis):
    """Vocab-parallel embedding lookup from a weight VALUE. In the
    hint-based path the plain gather + the (mp, None) weight spec let
    GSPMD insert the collective; inside a manual-mp shard_map region
    (the TP/PP serving steps) ``w`` is the local vocab-row shard, so
    this is the reference's masked local lookup + psum
    (mp_layers.py:47) — bitwise equal to the replicated gather, because
    exactly one shard contributes each row and the rest add zeros."""
    if axis is not None:
        from ..distributed.fleet.mp_layers import current_manual_mp
        if current_manual_mp() == axis:
            per = w.shape[0]
            local = input_ids - jax.lax.axis_index(axis) * per
            ok = (local >= 0) & (local < per)
            rows = jnp.take(w, jnp.clip(local, 0, per - 1), axis=0)
            rows = jnp.where(ok[..., None], rows, 0)
            return jax.lax.psum(rows, axis)
    return F.embedding(input_ids, w)


def _mp_gather_logits(logits, axis):
    """all_gather of the vocab-sharded logits inside a manual-mp region
    (both the untied lm_head and the tied embed.T shard vocab on mp) —
    the ONE gather per TP step; sampling then sees replicated values on
    every shard, keeping the fold_in(key, token_index) contract."""
    if axis is not None:
        from ..distributed.fleet.mp_layers import current_manual_mp
        if current_manual_mp() == axis:
            return jax.lax.all_gather(logits, axis, axis=-1, tiled=True)
    return logits


def _lora_delta(x, lora, name):
    """Gathered per-row LoRA delta for projection ``name`` (S-LoRA /
    Punica batched-adapter form, serving/lora.py). ``lora`` =
    ``(table [b] int32, params {name: (A [max_live, in, r],
    B [max_live, r, out])}, scales [max_live] f32)`` — the table is an
    array VALUE, so adapter churn in the serving engine never retraces.
    The low-rank path runs in fp32 regardless of the base dtype (an
    int8 base weight composes with a full-precision delta); row b
    computes ``(x_b @ A[t_b]) @ B[t_b] * scale[t_b]``, and slot 0's
    all-zero A/B + zero scale make the base-model delta exactly zero.
    Returns None when the target is absent."""
    table, params, scales = lora
    ab = params.get(name)
    if ab is None:
        return None
    A, B = ab
    xf = x.astype(jnp.float32)
    h = jnp.einsum("bsi,bir->bsr", xf, A[table].astype(jnp.float32))
    d = jnp.einsum("bsr,bro->bso", h, B[table].astype(jnp.float32))
    return d * scales[table][:, None, None]


def _apply_lora(y, x, lora, name):
    """Add projection ``name``'s LoRA delta (computed from the
    projection INPUT ``x``) onto the base output ``y``; no-op without
    an adapter spec or target."""
    if lora is None:
        return y
    d = _lora_delta(x, lora, name)
    return y if d is None else y + d.astype(y.dtype)


def _lora_layer(lora, i):
    """Slice the per-layer view of the gathered adapter buffers: layer
    ``i`` of every target's ``[max_live, L, in, r]`` stack (i is a
    Python int — the layer loop is unrolled under jit)."""
    if lora is None:
        return None
    table, params, scales = lora
    return (table, {t: (a[:, i], b[:, i]) for t, (a, b) in params.items()},
            scales)


def apply_rotary_pos_emb(x, cos, sin, position_ids=None):
    """x: [b, s, h, d]; cos/sin: [S, d/2] (parity:
    incubate fused_rotary_position_embedding — here one fused XLA graph)."""
    s = x.shape[1]
    if position_ids is None:
        c = cos[:s][None, :, None, :]
        si = sin[:s][None, :, None, :]
    else:
        c = jnp.take(cos, position_ids, axis=0)[:, :, None, :]
        si = jnp.take(sin, position_ids, axis=0)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * c - xf2 * si, xf2 * c + xf1 * si], axis=-1)
    return out.astype(x.dtype)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        h, kvh, d = (config.num_attention_heads, config.num_key_value_heads,
                     config.head_dim)
        mp = config.mp_axis
        init = I.XavierNormal()
        self.q_proj = nn.Linear(config.hidden_size, h * d, bias_attr=False,
                                weight_spec=(None, mp))
        self.k_proj = nn.Linear(config.hidden_size, kvh * d, bias_attr=False,
                                weight_spec=(None, mp))
        self.v_proj = nn.Linear(config.hidden_size, kvh * d, bias_attr=False,
                                weight_spec=(None, mp))
        self.o_proj = nn.Linear(h * d, config.hidden_size, bias_attr=False,
                                weight_spec=(mp, None))

    def forward(self, x, cos, sin, attn_mask=None, kv_cache=None, position_offset=0,
                paged=None, lora=None):
        b, s, _ = x.shape
        cfg = self.config
        d = cfg.head_dim
        # head counts come from the projection widths, not the config:
        # inside a manual-mp shard_map region (ServingEngine(tp=N)) the
        # weights are the per-shard columns — h/tp and kvh/tp heads — and
        # every branch below is head-local (the GQA ratio h/kvh survives
        # because both divide by tp). Unsharded, local == global.
        q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        if lora is not None:
            q = _apply_lora(q, x, lora, "q_proj")
            k = _apply_lora(k, x, lora, "k_proj")
            v = _apply_lora(v, x, lora, "v_proj")

        def _out_proj(t):
            # o_proj + its LoRA delta; the delta lands AFTER the mp
            # psum — the full-width input would otherwise be reduced
            # once per shard (delta × mp_degree)
            y = _mp_psum(self.o_proj(t), cfg.mp_axis)
            return _apply_lora(y, t, lora, "o_proj")
        h, kvh = q.shape[-1] // d, k.shape[-1] // d
        q = q.reshape(b, s, h, d)
        k = k.reshape(b, s, kvh, d)
        v = v.reshape(b, s, kvh, d)
        if paged is not None:
            # slot-indexed decode over a paged KV pool (the serving engine's
            # one-compiled-program step): b is the fixed slot count. s == 1
            # is the plain decode step; s > 1 is the speculative VERIFY
            # step, where per-slot row j is written at pool position
            # seq_lens + j and attends causally up to itself. ``paged`` =
            # (block_tables [b, max_pages] int32, seq_lens [b] int32,
            # active [b] bool[, n_live [b] int32]); the optional n_live
            # masks per-slot live rows — rows j >= n_live (padding beyond
            # a slot's draft count) write to the reserved scratch page 0
            # like inactive slots do, so rejected/padded drafts never land
            # in the pool and per-slot draft counts never retrace.
            # ``kv_cache`` is this layer's (pool_k, pool_v)
            # [num_pages, page_size, kvh, d].
            tables, seq_lens, active = paged[:3]
            n_live = paged[3] if len(paged) > 3 else None
            pos = jnp.broadcast_to(seq_lens[:, None] + jnp.arange(s)[None, :],
                                   (b, s))
            q = apply_rotary_pos_emb(q, cos, sin, pos)
            k = apply_rotary_pos_emb(k, cos, sin, pos)
            out, (pk, pv) = F.paged_attention_write_attend(
                q, k, v, kv_cache, tables, seq_lens, pos, active, n_live)
            return _out_proj(out.reshape(b, s, h * d)), (pk, pv)
        # sequence parallelism: when tracing inside a manual-sep shard_map
        # region (the pipelined train step), x is the LOCAL seq shard —
        # rope positions are offset by the shard start and attention runs
        # as ring attention over the sep axis (parity: segment_parallel.py:26,
        # here with cross-shard causal handled in LSE space).
        from ..distributed import sequence_parallel as _sp
        sep = cfg.sep_axis
        if sep is not None and _sp.current_manual_sep() == sep and kv_cache is None:
            if attn_mask is not None:
                raise NotImplementedError(
                    "sep ring attention is causal-only; attn_mask is not "
                    "supported on the sequence-sharded path")
            off = jax.lax.axis_index(sep) * s
            pos = jnp.broadcast_to(off + jnp.arange(s)[None, :], (b, s))
            q = apply_rotary_pos_emb(q, cos, sin, pos)
            k = apply_rotary_pos_emb(k, cos, sin, pos)
            # GQA k/v stay at kvh heads — ring_attention_manual repeats
            # per-step so rotating buffers are h/kvh smaller
            with jax.named_scope("core"):
                out = _sp.ring_attention_manual(q, k, v, axis=sep,
                                                causal=True)
            return _out_proj(out.reshape(b, s, h * d))
        static_zero = not isinstance(position_offset, jax.Array) and position_offset == 0
        if static_zero:
            q = apply_rotary_pos_emb(q, cos, sin)
            k = apply_rotary_pos_emb(k, cos, sin)
        else:  # offset may be a TRACED scalar: the jitted decode step and
            # the serving engine's suffix-only prefill both feed the
            # cached-context length here as an array argument, so a
            # varying prefix-cache hit length never retraces (SERVING.md
            # "Prefix caching") — rope rows are selected by value
            # (jnp.take, bitwise-equal to the static slice) and the
            # cache mask below derives from the same offset
            pos = position_offset + jnp.arange(s)[None, :]
            pos = jnp.broadcast_to(pos, (b, s))
            q = apply_rotary_pos_emb(q, cos, sin, pos)
            k = apply_rotary_pos_emb(k, cos, sin, pos)
        new_cache = None
        if kv_cache is not None and s == 1 and attn_mask is None:
            # single-token decode: fused masked MHA over the fixed cache
            # (parity: incubate masked_multihead_attention decode kernel)
            from ..incubate.nn import functional as FF
            seq_lens = jnp.broadcast_to(jnp.asarray(position_offset), (b,))
            with jax.named_scope("core"):
                out, ck, cv = FF.masked_multihead_attention(
                    q, k, v, kv_cache[0], kv_cache[1], seq_lens)
            return _out_proj(out.reshape(b, s, h * d)), (ck, cv)
        if kv_cache is not None:
            ck, cv = kv_cache
            from ..quantization.serving import (QuantizedKV, kv_dequantize,
                                                kv_quantize)
            if isinstance(ck, QuantizedKV):
                # int8 cache: quantize the written tokens (same per-row
                # absmax codes a later decode append would produce); the
                # cache keeps int8 + scales, attention dequantizes to fp32
                kq, vq = kv_quantize(k), kv_quantize(v)
                ck = QuantizedKV(
                    jax.lax.dynamic_update_slice_in_dim(
                        ck.q, kq.q, position_offset, axis=1),
                    jax.lax.dynamic_update_slice_in_dim(
                        ck.scale, kq.scale, position_offset, axis=1))
                cv = QuantizedKV(
                    jax.lax.dynamic_update_slice_in_dim(
                        cv.q, vq.q, position_offset, axis=1),
                    jax.lax.dynamic_update_slice_in_dim(
                        cv.scale, vq.scale, position_offset, axis=1))
                k, v = kv_dequantize(ck), kv_dequantize(cv)
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    ck, k.astype(ck.dtype), position_offset, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cv, v.astype(cv.dtype), position_offset, axis=1)
                k, v = ck, cv
            new_cache = (ck, cv)
            if attn_mask is None:
                # cached (pre)fill: row j sits at cache position
                # position_offset + j. Routed through the SAME grouped
                # GQA core as the paged decode/verify/chunk rows
                # (cached_prefill_attention -> _grouped_decode_attn), so
                # generate()'s prefill and the serving engine's chunked
                # prefill are one numeric program — the bitwise
                # engine==generate parity contract composes with chunk
                # boundaries. QuantizedKV caches pass through undequantized;
                # the core dequantizes them itself.
                seq_lens = jnp.broadcast_to(jnp.asarray(position_offset), (b,))
                with jax.named_scope("core"):
                    out = F.cached_prefill_attention(q, new_cache[0],
                                                     new_cache[1], seq_lens)
                return _out_proj(out.reshape(b, s, h * d)), new_cache
        if kvh != h:  # GQA: repeat kv heads
            rep = h // kvh
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if kv_cache is not None:
            # decode/prefill over the fixed-size cache buffer: query t sees
            # cache positions <= position_offset + t (zeros beyond are masked)
            q_pos = position_offset + jnp.arange(s)
            k_pos = jnp.arange(k.shape[1])
            cache_mask = k_pos[None, None, None, :] <= q_pos[None, None, :, None]
            attn_mask = cache_mask if attn_mask is None else (attn_mask & cache_mask)
            causal = False
        else:
            causal = True
        with jax.named_scope("core"):
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask,
                                                 is_causal=causal,
                                                 training=self.training)
        out = _out_proj(out.reshape(b, s, h * d))
        return (out, new_cache) if kv_cache is not None else out


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        mp = config.mp_axis
        self.gate_proj = nn.Linear(config.hidden_size, config.intermediate_size,
                                   bias_attr=False, weight_spec=(None, mp))
        self.up_proj = nn.Linear(config.hidden_size, config.intermediate_size,
                                 bias_attr=False, weight_spec=(None, mp))
        self.down_proj = nn.Linear(config.intermediate_size, config.hidden_size,
                                   bias_attr=False, weight_spec=(mp, None))

    def forward(self, x, lora=None):
        # SwiGLU (parity: incubate swiglu fused op — XLA fuses this chain)
        if lora is None:
            y = self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))
            return _mp_psum(y, self.config.mp_axis)
        g = _apply_lora(self.gate_proj(x), x, lora, "gate_proj")
        u = _apply_lora(self.up_proj(x), x, lora, "up_proj")
        t = F.silu(g) * u
        # down_proj's delta lands AFTER the mp psum (see _out_proj)
        y = _mp_psum(self.down_proj(t), self.config.mp_axis)
        return _apply_lora(y, t, lora, "down_proj")


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)

    def forward(self, x, cos, sin, attn_mask=None, kv_cache=None, position_offset=0,
                paged=None, lora=None):
        # the scopes name the layer part in every operation's metadata
        # (``op_name``), with no layer index: a trace's reduction merges
        # the same part of every layer
        res = x
        with jax.named_scope("norm"):
            h = self.input_layernorm(x)
        with jax.named_scope("attn"):
            if kv_cache is not None:
                h, new_cache = self.self_attn(h, cos, sin, attn_mask,
                                              kv_cache, position_offset,
                                              paged, lora)
            else:
                h = self.self_attn(h, cos, sin, attn_mask, lora=lora)
                new_cache = None
        x = res + h
        res = x
        with jax.named_scope("norm"):
            h = self.post_attention_layernorm(x)
        with jax.named_scope("mlp"):
            x = res + self.mlp(h, lora=lora)
        return (x, new_cache) if kv_cache is not None else x


class LlamaModel(Layer):
    @scoped_dtype_init
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        # vocab-parallel embedding: shard vocab rows on mp (parity:
        # VocabParallelEmbedding mp_layers.py:47)
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size,
                                         weight_spec=(config.mp_axis, None))
        self.layers = nn.LayerList([LlamaDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = _rope_cache(config)
        self.register_buffer("rope_cos", cos, persistable=False)
        self.register_buffer("rope_sin", sin, persistable=False)

    def _embed(self, input_ids):
        """Vocab-parallel embedding (see :func:`_vocab_parallel_embed`;
        routed through the shared helper so the pipeline-staged serving
        forward embeds bitwise-identically)."""
        mp = self.config.mp_axis
        if mp is not None:
            from ..distributed.fleet.mp_layers import current_manual_mp
            if current_manual_mp() == mp:
                return _vocab_parallel_embed(self.embed_tokens.weight,
                                             input_ids, mp)
        return self.embed_tokens(input_ids)

    def forward(self, input_ids, attn_mask=None, kv_caches=None, position_offset=0,
                paged=None, lora=None):
        with jax.named_scope("embed"):
            x = self._embed(input_ids)
        cos, sin = self.rope_cos, self.rope_sin
        new_caches = []
        for i, layer in enumerate(self.layers):
            if kv_caches is not None:
                x, c = layer(x, cos, sin, attn_mask, kv_caches[i], position_offset,
                             paged, _lora_layer(lora, i))
                new_caches.append(c)
            elif (self.config.recompute and self.training
                  and i % max(self.config.recompute_interval, 1) == 0):
                # trade FLOPs for HBM: re-run the layer in backward
                if self.config.recompute_policy == "dots":
                    policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                    x = jax.checkpoint(
                        lambda x, layer=layer: layer(x, cos, sin, attn_mask),
                        policy=policy)(x)
                else:
                    x = jax.checkpoint(
                        lambda x, layer=layer: layer(x, cos, sin, attn_mask))(x)
            else:
                x = layer(x, cos, sin, attn_mask)
        with jax.named_scope("norm"):
            x = self.norm(x)
        return (x, new_caches) if kv_caches is not None else x


class LlamaForCausalLM(Layer):
    @scoped_dtype_init
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False,
                                     weight_spec=(None, config.mp_axis))

    def forward(self, input_ids, attn_mask=None, kv_caches=None, position_offset=0,
                paged=None, lora=None):
        out = self.model(input_ids, attn_mask, kv_caches, position_offset, paged,
                         lora)
        if kv_caches is not None:
            hidden, new_caches = out
        else:
            hidden = out
        with jax.named_scope("lm_head"):
            if self.config.tie_word_embeddings:
                logits = hidden @ self.model.embed_tokens.weight.T
            else:
                logits = self.lm_head(hidden)
            logits = _mp_gather_logits(logits, self.config.mp_axis)
        return (logits, new_caches) if kv_caches is not None else logits

    def pp_parts(self):
        """The embed / stacked-layers / head decomposition the
        pipeline-parallel serving engine stages over a 'pp' mesh axis
        (serving/parallel.py TPContext, pp>1). ``embed``/``head`` are
        closures over a path-keyed state dict — the SAME expressions
        ``forward`` runs (shared ``_vocab_parallel_embed``, rms_norm +
        tied/untied head matmul + the one mp logits gather), so the
        staged forward is bitwise-equal to the flat one. ``template`` is
        layer 0 — every decoder layer is isomorphic, so one
        functional_call per stacked slice replays any layer."""
        cfg = self.config

        def embed(state, input_ids):
            with jax.named_scope("embed"):
                return _vocab_parallel_embed(
                    state["model.embed_tokens.weight"], input_ids,
                    cfg.mp_axis)

        def head(state, hidden):
            with jax.named_scope("norm"):
                hidden = F.rms_norm(hidden, state["model.norm.weight"],
                                    cfg.rms_norm_eps)
            with jax.named_scope("lm_head"):
                if cfg.tie_word_embeddings:
                    logits = hidden @ state["model.embed_tokens.weight"].T
                else:
                    logits = F.linear(hidden, state["lm_head.weight"])
                return _mp_gather_logits(logits, cfg.mp_axis)

        return {
            "layer_prefix": "model.layers.",
            "num_layers": cfg.num_hidden_layers,
            "template": self.model.layers[0],
            "rope_keys": ("model.rope_cos", "model.rope_sin"),
            "embed": embed,
            "head": head,
        }

    def init_kv_caches(self, batch_size, max_len, dtype=None):
        """Fixed-size contiguous caches; ``dtype="int8"`` (or jnp.int8)
        builds QuantizedKV caches — int8 codes + fp32 absmax scales —
        written at cache-write time and dequantized at read time
        (quantization/serving.py)."""
        cfg = self.config
        dtype = dtype or jnp.bfloat16
        shape = (batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim)
        if jnp.dtype(dtype) == jnp.int8:
            from ..quantization.serving import QuantizedKV

            def _zeros():
                return QuantizedKV(jnp.zeros(shape, jnp.int8),
                                   jnp.zeros(shape[:3], jnp.float32))
            return [(_zeros(), _zeros())
                    for _ in range(cfg.num_hidden_layers)]
        return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                for _ in range(cfg.num_hidden_layers)]

    def decode_cache_stats(self) -> dict:
        """Public view of the compiled decode-program cache (the supported
        replacement for poking ``_decode_prog_cache``): ``signatures`` is
        the number of distinct (batch, prompt_len, new_tokens, sampling)
        signatures holding compiled (prefill, decode, step) triples,
        ``capacity`` the LRU bound, ``signature_keys`` the cached keys in
        LRU order (oldest first). A serving loop should see ``signatures``
        stay flat — growth means unbucketed prompt shapes are retracing."""
        cache = self.__dict__.get("_decode_prog_cache") or {}
        return {"signatures": len(cache), "capacity": 16,
                "signature_keys": list(cache.keys())}

    def decode_programs(self, b: int, s0: int, max_new_tokens: int,
                        max_len: int | None = None, do_sample: bool = False,
                        top_p: float = 1.0, temperature: float = 1.0,
                        eos_token_id: int | None = None,
                        pad_token_id: int | None = None):
        """Build (and cache per signature) the compiled serving programs:

        - ``prefill(state, ids, caches, key) -> (tok, caches)`` — one
          forward over the prompt, filling the KV cache;
        - ``decode(state, tok, caches, keys) -> toks`` — the WHOLE
          ``max_new_tokens - 1`` token loop as ONE jitted ``lax.scan`` over
          the fixed-size cache (each iteration routes through the fused
          masked-MHA decode path);
        - ``step(state, tok, caches, pos, key) -> (tok, caches)`` — a
          single decode step (the eager debugging loop).

        Cached on the instance (LRU, 16 signatures) so repeated
        ``generate()`` calls (a serving loop) reuse the executables instead
        of retracing — the analogue of the reference predictor's program
        reuse (analysis_predictor.cc:1423). The bound matters: a server
        fed unbucketed prompt lengths would otherwise pin one compiled
        scan program per distinct (batch, prompt_len) forever; bucket
        prompts to a few lengths to stay inside the cache."""
        from collections import OrderedDict

        from ..nn.module import functional_call
        from ..ops.random import top_p_sampling
        max_len = max_len or (s0 + max_new_tokens)
        pad_token_id = pad_token_id if pad_token_id is not None else eos_token_id
        sig = (b, s0, max_new_tokens, max_len, do_sample, float(top_p),
               float(temperature), eos_token_id, pad_token_id)
        cache = self.__dict__.setdefault("_decode_prog_cache", OrderedDict())
        if sig in cache:
            cache.move_to_end(sig)
            return cache[sig]

        def pick(logits, key):
            if not do_sample:
                return jnp.argmax(logits, axis=-1)
            probs = jax.nn.softmax(logits.astype(jnp.float32) / temperature, -1)
            _, idx = top_p_sampling(probs, jnp.full((b,), top_p), key=key)
            return idx[:, 0]

        @jax.jit
        def prefill(state, ids, caches, key, lora=None):
            (logits, caches), _ = functional_call(
                self, state, ids, None, caches, 0, lora=lora,
                training=False)
            return pick(logits[:, -1], key), caches

        @jax.jit
        def decode(state, tok, caches, keys, lora=None):
            def body(carry, xs):
                tok, caches, done = carry
                key, pos = xs
                (logits, caches), _ = functional_call(
                    self, state, tok[:, None], None, caches, pos,
                    lora=lora, training=False)
                nt = pick(logits[:, -1], key)
                if eos_token_id is not None:
                    # once a row emits EOS, its later tokens pin to pad
                    # INSIDE the scan (the serving engine keys per-request
                    # stop off the same mask)
                    nt = jnp.where(done, jnp.int32(pad_token_id),
                                   nt.astype(jnp.int32))
                    done = done | (nt == eos_token_id)
                return (nt, caches, done), nt
            done0 = (tok == eos_token_id if eos_token_id is not None
                     else jnp.zeros((b,), bool))
            positions = s0 + jnp.arange(max_new_tokens - 1)
            (tok, caches, _), toks = jax.lax.scan(
                body, (tok, caches, done0), (keys, positions))
            return toks  # [max_new_tokens - 1, b]

        @jax.jit
        def step(state, tok, caches, pos, key, lora=None):
            (logits, caches), _ = functional_call(
                self, state, tok[:, None], None, caches, pos, lora=lora,
                training=False)
            return pick(logits[:, -1], key), caches

        cache[sig] = (prefill, decode, step)
        while len(cache) > 16:
            cache.popitem(last=False)
        return cache[sig]

    def generate(self, input_ids, max_new_tokens: int = 32, max_len: int | None = None,
                 do_sample: bool = False, top_p: float = 1.0,
                 temperature: float = 1.0, seed: int | None = None,
                 jit_loop: bool = True, eos_token_id: int | None = None,
                 pad_token_id: int | None = None, kv_dtype=None, lora=None):
        """Decode: one jitted prefill + the WHOLE token loop as one jitted
        ``lax.scan`` over the fixed-size KV cache (decode routes through the
        fused masked-MHA path). Two compiled programs total — no per-token
        host dispatch is left in the decode loop (parity: AnalysisPredictor /
        FusedMultiTransformer generation, analysis_predictor.cc:1423); the
        programs are cached on the model, so a serving loop of generate()
        calls never retraces.

        ``jit_loop=False`` keeps the one-compiled-step-per-token eager loop
        (token-by-token debugging, early-exit experimentation); both paths
        produce identical tokens with greedy decoding.

        do_sample=True draws each token with nucleus sampling via
        ``ops.random.top_p_sampling`` (parity: tensor/search.py:1235 feeding
        the reference's sampling decode); default is greedy argmax.

        ``eos_token_id``: once a row emits EOS, its subsequent tokens are
        pinned to ``pad_token_id`` (default: the EOS id) inside the scan —
        output shape stays static [b, s0 + max_new_tokens].

        ``kv_dtype``: cache storage dtype — ``"int8"`` decodes over a
        quantized contiguous cache (the reference arm the serving
        engine's int8 parity tests compare against).

        ``lora``: a ``(table, params, scales)`` adapter spec (e.g.
        ``AdapterPool.lora_ref([slot] * b)``, serving/lora.py): every
        projection gains its gathered low-rank delta through the SAME
        ``_lora_delta`` graph the serving engine's compiled steps run —
        the single-request reference arm of the engine==generate
        bitwise parity contract, now per adapter."""
        input_ids = jnp.asarray(input_ids)
        b, s0 = input_ids.shape
        max_len = max_len or (s0 + max_new_tokens)
        state = self.state_dict(include_non_persistable_buffer=True)
        caches = self.init_kv_caches(b, max_len, dtype=kv_dtype)
        key0 = jax.random.key(seed if seed is not None else 0)
        prefill, decode, step = self.decode_programs(
            b, s0, max_new_tokens, max_len, do_sample, top_p, temperature,
            eos_token_id, pad_token_id)
        pad = pad_token_id if pad_token_id is not None else eos_token_id

        keys = jax.random.split(key0, max_new_tokens)
        tok, caches = prefill(state, input_ids, caches, keys[0], lora)
        if max_new_tokens == 1:
            return jnp.concatenate([input_ids, tok[:, None]], axis=1)
        if jit_loop:
            toks = decode(state, tok, caches, keys[1:], lora)
            new = jnp.concatenate([tok[:, None], toks.T], axis=1)
            return jnp.concatenate([input_ids, new], axis=1)

        out = [tok]
        done = (tok == eos_token_id) if eos_token_id is not None else None
        for i in range(1, max_new_tokens):
            tok, caches = step(state, tok, caches, s0 + i - 1, keys[i], lora)
            if eos_token_id is not None:  # same pinning as the scan path
                tok = jnp.where(done, jnp.int32(pad), tok.astype(jnp.int32))
                done = done | (tok == eos_token_id)
            out.append(tok)
        return jnp.concatenate([input_ids, jnp.stack(out, axis=1)], axis=1)

    def loss(self, logits, labels, ignore_index=-100):
        """Shifted causal-LM cross entropy (parity: ParallelCrossEntropy for
        the TP case — GSPMD handles the vocab-sharded softmax reduction)."""
        shift_logits = logits[:, :-1]
        shift_labels = labels[:, 1:]
        return F.cross_entropy(
            shift_logits.reshape(-1, shift_logits.shape[-1]),
            shift_labels.reshape(-1), ignore_index=ignore_index)

    def num_params(self):
        import numpy as np
        return int(sum(np.prod(v.shape) for v in self.param_dict().values()))


def llama_tiny(**kw):
    """Test-scale config."""
    return LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=384,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, max_position_embeddings=512, **kw)


def llama_2_7b(**kw):
    return LlamaConfig(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                       num_hidden_layers=32, num_attention_heads=32,
                       num_key_value_heads=32, **kw)


def llama_3_8b(**kw):
    return LlamaConfig(vocab_size=128256, hidden_size=4096,
                       intermediate_size=14336, num_hidden_layers=32,
                       num_attention_heads=32, num_key_value_heads=8,
                       max_position_embeddings=8192, rope_theta=500000.0, **kw)
