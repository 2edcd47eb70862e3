"""openPangu-Ultra-MoE decoder (``model_type`` ``pangu_ultra_moe``;
published description: the config.json of
huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B and the
multi-head latent attention / shared-expert design it follows): a stack
of blocks with FOUR norms each (``sandwich_norm``),

    a = N_post_attn(MLA(N_in(x)));      x = x + a
    m = N_post_mlp(FFN(N_pre_mlp(x)));  x = x + m

- MLA, multi-head latent attention: the query through a low-rank pair
  (``q_lora_rank``) into heads of ``qk_nope_head_dim | qk_rope_head_dim``;
  keys and values through ONE normed latent of ``kv_lora_rank`` a token
  beside one rotated key of ``qk_rope_head_dim`` shared by every head.
  That pair is all a served token keeps: ``cache_layers()`` declares
  ``("latent", kv_lora_rank + qk_rope_head_dim)``.
- FFN: a gated SiLU MLP in the first ``first_k_dense_replace`` layers,
  after them this chip's share of ``n_routed_experts`` gated experts
  and one gated shared expert (``distributed.moe.HeldExpertsMoE``).

Serving only. ``forward(input_ids)`` is the cache-free pass in the
published, UNABSORBED form (per-head keys and values from the latent);
``forward(toks, None, cache, 0, paged)`` is the call ``ServingEngine``'s
two step programs make and attends AGAINST the cached latent (absorbed:
the query is carried through the key up-projection, the mix of latent
rows through the value up-projection), so that a slot's cache is never
decompressed to per-head K/V. ``cache`` is a
``serving.kv_cache.HybridCache`` whose ``kv`` holds one ``(pool,)`` a
layer and whose ``state`` is empty. No training path and no multi-token
prediction module.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dtypes import scoped_dtype_init
from ..distributed.moe import HeldExpertsMoE
from ..nn import functional as F
from ..nn.module import Layer

__all__ = ["PanguMoEConfig", "PanguMoEForCausalLM", "PanguMLAttention",
           "rope_rotate", "pangu_moe_tiny"]


@dataclass
class PanguMoEConfig:
    """The published keys of a ``pangu_ultra_moe`` config.json (defaults:
    openPangu-Ultra-MoE-718B), plus ``experts_held`` ``(first, count)``:
    the routed experts this chip holds (the router stays
    ``n_routed_experts`` wide), and one boolean for each thing the
    config does not say, the default being the family's published
    modelling code and the other value the other reading:
    ``router_score_bias`` (a correction bias that chooses experts but
    does not weigh them), ``rope_interleave`` (the rotary pairs are
    neighbours ``(2i, 2i + 1)``, not the two halves),
    ``post_norm_on_output`` (``N_post_*`` act on the sublayer's output
    before the residual add, not on the sum after it)."""
    vocab_size: int = 153600
    hidden_size: int = 7680
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    intermediate_size: int = 18432
    # multi-head latent attention
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 25600000.0
    max_position_embeddings: int = 131072
    rope_interleave: bool = False
    # experts
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    router_score_bias: bool = False
    experts_held: tuple | None = None
    # norms, head
    rms_norm_eps: float = 1e-5
    post_norm_on_output: bool = True
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} is not "
                f"within the {self.num_hidden_layers} layers")
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)

    @property
    def latent_row_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    def cache_layers(self) -> list:
        """What each layer keeps for a request being served
        (``serving.kv_cache`` builds the pool from it): one row of the
        normed latent beside the rotated shared key."""
        return [("latent", self.latent_row_width)] * self.num_hidden_layers


def rope_rotate(x, pos, theta: float, interleave: bool = False):
    """Rotary embedding of x [b, s, n, d] at positions ``pos`` [b, s],
    plain (no scaling), computed from the positions. Rotate-half; with
    ``interleave`` the pairs are the neighbours ``(2i, 2i + 1)``, which
    are gathered into halves first (q and k alike, so their products are
    those of the interleaved form)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[..., None].astype(jnp.float32) * inv             # [b, s, d/2]
    c, s = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


class PanguMLAttention(Layer):
    def __init__(self, config: PanguMoEConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        c = config
        h = c.num_attention_heads
        self.q_a_proj = nn.Linear(c.hidden_size, c.q_lora_rank,
                                  bias_attr=False)
        self.q_a_layernorm = nn.RMSNorm(c.q_lora_rank, c.rms_norm_eps)
        self.q_b_proj = nn.Linear(
            c.q_lora_rank, h * (c.qk_nope_head_dim + c.qk_rope_head_dim),
            bias_attr=False)
        self.kv_a_proj_with_mqa = nn.Linear(
            c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim,
            bias_attr=False)
        self.kv_a_layernorm = nn.RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        # per head (k_nope | v) from the latent
        self.kv_b_proj = nn.Linear(
            c.kv_lora_rank, h * (c.qk_nope_head_dim + c.v_head_dim),
            bias_attr=False)
        self.o_proj = nn.Linear(h * c.v_head_dim, c.hidden_size,
                                bias_attr=False)

    def forward(self, u, cache=None, paged=None):
        """u [b, s, hidden]. Cache-free: the unabsorbed form, causal.
        Served: ``cache`` the layer's ``(pool,)``, ``paged`` the step's
        ``(tables, seq_lens, active[, n_live])``; returns the output and
        the new ``(pool,)``."""
        c = self.config
        b, s, _ = u.shape
        h, dn, dr, dv, r = (c.num_attention_heads, c.qk_nope_head_dim,
                            c.qk_rope_head_dim, c.v_head_dim,
                            c.kv_lora_rank)
        scale = 1.0 / math.sqrt(dn + dr)
        if paged is None:
            pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        else:
            tables, seq_lens, active = paged[:3]
            n_live = paged[3] if len(paged) > 3 else None
            pos = jnp.broadcast_to(
                seq_lens[:, None] + jnp.arange(s)[None, :], (b, s))
        with jax.named_scope("q_lora"):
            q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(u)))
            q_nope, q_rope = jnp.split(q.reshape(b, s, h, dn + dr), [dn],
                                       axis=-1)
            q_rope = rope_rotate(q_rope, pos, c.rope_theta,
                                 c.rope_interleave)
        with jax.named_scope("kv_lora"):
            ckv, kr = jnp.split(self.kv_a_proj_with_mqa(u), [r], axis=-1)
            lat = self.kv_a_layernorm(ckv)                       # [b, s, r]
            kr = rope_rotate(kr[:, :, None, :], pos, c.rope_theta,
                             c.rope_interleave)               # [b, s, 1, dr]
        w_uk, w_uv = jnp.split(
            self.kv_b_proj.weight.reshape(r, h, dn + dv), [dn], axis=-1)
        if paged is None:
            with jax.named_scope("core"):
                k_nope = jnp.einsum("bsr,rhd->bshd", lat, w_uk)
                v = jnp.einsum("bsr,rhd->bshd", lat, w_uv)
                sc = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bqhd,bkd->bhqk", q_rope, kr[:, :, 0],
                                   preferred_element_type=jnp.float32))
                sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                               sc * scale, -jnp.inf)
                p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
                o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
            with jax.named_scope("out"):
                return self.o_proj(o.reshape(b, s, h * dv))
        with jax.named_scope("absorb"):
            q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
        o_lat, cache = F.paged_latent_write_attend(
            jnp.concatenate([q_lat, q_rope], axis=-1),
            jnp.concatenate([lat, kr[:, :, 0]], axis=-1), cache, tables,
            seq_lens, pos, active, n_live, v_width=r, scale=scale)
        with jax.named_scope("absorb"):
            o = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)
        with jax.named_scope("out"):
            return self.o_proj(o.reshape(b, s, h * dv)), cache


class PanguMLP(Layer):
    def __init__(self, config: PanguMoEConfig):
        super().__init__(dtype=config.dtype)
        hid, f = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(hid, f, bias_attr=False)
        self.up_proj = nn.Linear(hid, f, bias_attr=False)
        self.down_proj = nn.Linear(f, hid, bias_attr=False)

    def forward(self, u):
        return self.down_proj(jax.nn.silu(self.gate_proj(u))
                              * self.up_proj(u))


class PanguMoEBlock(Layer):
    def __init__(self, config: PanguMoEConfig, layer_idx: int):
        super().__init__(dtype=config.dtype)
        c = config
        self.post_norm_on_output = c.post_norm_on_output
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.pre_mlp_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.post_mlp_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = PanguMLAttention(c)
        self.is_moe = layer_idx >= c.first_k_dense_replace
        if self.is_moe:
            self.mlp = HeldExpertsMoE(
                c.hidden_size, None, c.moe_intermediate_size,
                c.n_routed_experts, c.num_experts_per_tok,
                experts_held=c.experts_held,
                d_shared=c.n_shared_experts * c.moe_intermediate_size,
                activation=jax.nn.silu, gated=True, shared_gated=True,
                norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor,
                score_bias=c.router_score_bias)
        else:
            self.mlp = PanguMLP(c)

    def _residual(self, x, out, norm):
        with jax.named_scope("norm"):
            return x + norm(out) if self.post_norm_on_output \
                else norm(x + out)

    def forward(self, x, cache=None, paged=None, live=None):
        """-> (x, the layer's new ``(pool,)`` or None, the expert
        layer's counters or None)."""
        with jax.named_scope("norm"):
            u = self.input_layernorm(x)
        with jax.named_scope("attn"):
            a = self.self_attn(u, cache, paged)
            if paged is not None:
                a, cache = a
        x = self._residual(x, a, self.post_attention_layernorm)
        with jax.named_scope("norm"):
            u = self.pre_mlp_layernorm(x)
        counts = None
        if self.is_moe:
            with jax.named_scope("moe"):
                m, counts = self.mlp(u, live)
        else:
            with jax.named_scope("mlp"):
                m = self.mlp(u)
        return self._residual(x, m, self.post_mlp_layernorm), cache, counts


class PanguMoEModel(Layer):
    @scoped_dtype_init
    def __init__(self, config: PanguMoEConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([
            PanguMoEBlock(config, i)
            for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)


class PanguMoEForCausalLM(Layer):
    @scoped_dtype_init
    def __init__(self, config: PanguMoEConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = PanguMoEModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, attn_mask=None, cache=None,
                position_offset=0, paged=None, lora=None):
        """Cache-free: ``forward(input_ids)`` -> logits [b, s, vocab].
        Served: ``forward(toks, None, cache, 0, (tables, seq_lens,
        active[, n_live]))`` -> (logits, the new ``HybridCache``, whose
        ``counts`` are the step's expert counters summed over the expert
        layers)."""
        if attn_mask is not None or lora is not None:
            raise NotImplementedError(
                "PanguMoEForCausalLM takes no attention mask and no LoRA")
        m = self.model
        with jax.named_scope("embed"):
            x = m.embed_tokens(input_ids)
        k = input_ids.shape[1]
        live, kv, counts = None, [], jnp.zeros((3,), jnp.int32)
        if cache is not None:
            active = paged[2]
            n_live = paged[3] if len(paged) > 3 else k
            live = active[:, None] & (jnp.arange(k)[None, :]
                                      < jnp.reshape(n_live, (-1, 1)))
        for i, block in enumerate(m.layers):
            x, entry, c = block(x, None if cache is None else cache.kv[i],
                                paged, live)
            kv.append(entry)
            if c is not None:
                counts = counts + c
        with jax.named_scope("norm"):
            x = m.norm(x)
        with jax.named_scope("lm_head"):
            logits = (x @ m.embed_tokens.weight.T
                      if self.config.tie_word_embeddings
                      else self.lm_head(x))
        if cache is None:
            return logits
        return logits, cache._replace(kv=kv, counts=counts)

    def num_params(self) -> int:
        return sum(int(v.size) for v in self.param_dict().values())


def pangu_moe_tiny(**kw) -> PanguMoEConfig:
    """A toy of the family for CPU tests: one dense and two expert
    layers, 16 experts of which 4 are held, a latent row of 32 + 16."""
    base = dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=3,
        first_k_dense_replace=1, intermediate_size=96,
        num_attention_heads=8, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
        rope_theta=10000.0, max_position_embeddings=512,
        n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, experts_held=(0, 4), dtype="float32")
    base.update(kw)
    return PanguMoEConfig(**base)
