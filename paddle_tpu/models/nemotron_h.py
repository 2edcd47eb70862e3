"""Nemotron-H hybrid decoder (``model_type`` ``nemotron_h``; published
description: huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16):
a stack whose layers are of three kinds by a pattern string, each a
pre-norm residual block around ONE mixer,
``x = x + mixer_i(rmsnorm(x))``:

- ``M`` Mamba-2 (``nn.functional.ssm``): in_proj to ``[z | xBC | dt]``, a
  depthwise causal conv over the last ``conv_kernel`` rows, the
  state-space recurrence per head, a gated grouped RMSNorm, out_proj;
- ``*`` grouped-query attention, no bias, through the same paged core as
  Llama's (``F.paged_attention_write_attend``);
- ``E`` LatentMoE (``distributed.moe.HeldExpertsMoE``): this chip's share
  of the routed experts in a latent width, and one shared expert.

Serving only: ``forward(input_ids)`` is the cache-free pass the tests
compare with the reference, and ``forward(toks, None, cache, 0, paged)``
is the call ``ServingEngine``'s two step programs make. ``cache`` is a
``serving.kv_cache.HybridCache``: K/V page pairs for the attention
layers, a per-slot ``(conv window, SSM state)`` pair for each Mamba
layer, both in layer order. A slot that starts at position 0 starts from
zero state (``fresh_slots``); a mixed step advances a slot's state by
its ``n_live`` rows and no more; an inactive slot's state is untouched.
No training path and no multi-token-prediction module.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dtypes import scoped_dtype_init
from ..distributed.moe import HeldExpertsMoE, relu2
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.functional.ssm import fresh_slots
from ..nn.module import Layer, Parameter
from .llama import LlamaConfig, _rope_cache, apply_rotary_pos_emb

__all__ = ["NemotronHConfig", "NemotronHForCausalLM", "NemotronHMamba2",
           "NemotronHAttention", "fresh_slots", "nemotron_h_tiny"]

MAMBA, ATTENTION, MOE = "M", "*", "E"


@dataclass
class NemotronHConfig:
    """The published keys of a ``nemotron_h`` config.json (defaults:
    NVIDIA-Nemotron-3-Super-120B-A12B-BF16), plus ``experts_held``
    ``(first, count)``: the routed experts this chip holds (the router
    stays ``n_routed_experts`` wide), and ``attention_rope``: whether the
    attention layers rotate q and k. The family carries position in its
    Mamba layers and its attention layers take none, although the config
    file has ``rope_theta``: ``False`` is that reading, ``True`` the
    other."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = ""
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    attention_rope: bool = False
    rope_theta: float = 10000.0
    max_position_embeddings: int = 262144
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    # LatentMoE
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 5.0
    experts_held: tuple | None = None
    # norms, head
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # state kept by a serving slot: the SSM state in float32 (the
    # family's model cards advise a float32 state cache), the conv
    # window in the model's dtype
    ssm_state_dtype: str = "float32"

    def __post_init__(self):
        pat = self.hybrid_override_pattern
        if len(pat) != self.num_hidden_layers or set(pat) - {MAMBA,
                                                            ATTENTION, MOE}:
            raise ValueError(
                f"hybrid_override_pattern {pat!r} must give one of "
                f"'M', '*', 'E' for each of {self.num_hidden_layers} layers")
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_d_inner + 2 * self.n_groups * self.ssm_state_size

    def cache_layers(self) -> list:
        """What each layer keeps for a request being served, in layer
        order (``serving.kv_cache`` builds the pool from it):
        ``("pages", kv heads, head dim)``, ``("state", ((shape, dtype),
        ...))`` for arrays kept per slot, or None."""
        state = ("state", (
            ((self.conv_kernel - 1, self.mamba_conv_dim), self.dtype),
            ((self.mamba_num_heads, self.mamba_head_dim,
              self.ssm_state_size), self.ssm_state_dtype)))
        pages = ("pages", self.num_key_value_heads, self.head_dim)
        return [{MAMBA: state, ATTENTION: pages, MOE: None}[c]
                for c in self.hybrid_override_pattern]


class NemotronHMamba2(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        c = config
        d_in = c.mamba_d_inner
        self.in_proj = nn.Linear(
            c.hidden_size, d_in + c.mamba_conv_dim + c.mamba_num_heads,
            bias_attr=None if c.mamba_proj_bias else False)
        # depthwise causal conv, [channels, taps], the last tap on the
        # current row
        self.conv1d_weight = Parameter(I.XavierNormal()(
            (c.mamba_conv_dim, c.conv_kernel), self._dtype))
        self.conv1d_bias = (Parameter(I.Constant(0.0)(
            (c.mamba_conv_dim,), self._dtype)) if c.use_conv_bias else None)
        h = c.mamba_num_heads
        self.A_log = Parameter(I.Constant(0.0)((h,), jnp.float32))
        self.dt_bias = Parameter(I.Constant(0.0)((h,), jnp.float32))
        self.D = Parameter(I.Constant(1.0)((h,), jnp.float32))
        self.norm_weight = Parameter(I.Constant(1.0)((d_in,), self._dtype))
        self.out_proj = nn.Linear(
            d_in, c.hidden_size,
            bias_attr=None if c.mamba_proj_bias else False)

    def _split(self, u):
        c = self.config
        d_in = c.mamba_d_inner
        z, xbc, dt = jnp.split(
            self.in_proj(u), [d_in, d_in + c.mamba_conv_dim], axis=-1)
        return z, xbc, dt

    def _gated_norm(self, y, z):
        """``rmsnorm(y * silu(z))`` over each of ``n_groups`` groups of
        channels, in float32."""
        c = self.config
        y = y * jax.nn.silu(z.astype(jnp.float32))
        shape = y.shape
        yg = y.reshape(*shape[:-1], c.n_groups, shape[-1] // c.n_groups)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                + c.layer_norm_epsilon)
        return (yg.reshape(shape)
                * self.norm_weight.astype(jnp.float32)).astype(z.dtype)

    def forward(self, u, state=None, live=None, fresh=None):
        """u [b, k, hidden]. ``state`` = (conv window [b, w - 1, c],
        SSM state [b, h, p, n]) or None (a sequence from its start);
        ``live`` [b, k] marks the rows that count (a prefix of each
        slot's rows), ``fresh`` [b] the slots that start from zero.
        Returns the block's output, and the new state where one came."""
        c = self.config
        b, k, _ = u.shape
        h, p, n, g = (c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size,
                      c.n_groups)
        f32 = jnp.float32
        z, xbc, dt = self._split(u)
        if state is None:
            window = jnp.zeros((b, c.conv_kernel - 1, c.mamba_conv_dim),
                               xbc.dtype)
            ssm = jnp.zeros((b, h, p, n), f32)
            live = jnp.ones((b, k), bool)
        else:
            window, ssm = state
            keep = ~fresh
            window = jnp.where(keep[:, None, None], window, 0)
            ssm = jnp.where(keep[:, None, None, None], ssm, 0)
        n_live = jnp.sum(live, axis=1).astype(jnp.int32)
        bias = (self.conv1d_bias if self.conv1d_bias is not None
                else jnp.zeros((c.mamba_conv_dim,), f32))
        with jax.named_scope("conv"):
            xbc, new_window = F.causal_conv1d_window(
                xbc, window, self.conv1d_weight, bias, n_live)
            xbc = jax.nn.silu(xbc)
        x, B, C = jnp.split(xbc, [h * p, h * p + g * n], axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + self.dt_bias)
        dt = jnp.where(live[..., None], dt, 0.0)   # a dead row: S as it was
        A = -jnp.exp(self.A_log)
        with jax.named_scope("scan"):
            if k == 1:
                y, new_ssm = F.ssd_step(
                    x.reshape(b, h, p), dt[:, 0], A, B.reshape(b, g, n),
                    C.reshape(b, g, n), self.D, ssm.astype(f32))
                y = y[:, None]
            else:
                y, new_ssm = F.ssd_chunk_scan(
                    x.reshape(b, k, h, p), dt, A, B.reshape(b, k, g, n),
                    C.reshape(b, k, g, n), self.D, ssm.astype(f32))
        out = self.out_proj(self._gated_norm(y.reshape(b, k, h * p), z))
        if state is None:
            return out
        return out, (new_window, new_ssm.astype(state[1].dtype))


class NemotronHAttention(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        h, kvh, d = (config.num_attention_heads, config.num_key_value_heads,
                     config.head_dim)
        bias = None if config.attention_bias else False
        self.q_proj = nn.Linear(config.hidden_size, h * d, bias_attr=bias)
        self.k_proj = nn.Linear(config.hidden_size, kvh * d, bias_attr=bias)
        self.v_proj = nn.Linear(config.hidden_size, kvh * d, bias_attr=bias)
        self.o_proj = nn.Linear(h * d, config.hidden_size, bias_attr=bias)
        if config.attention_rope:
            cos, sin = _rope_cache(LlamaConfig(
                hidden_size=h * d, num_attention_heads=h,
                max_position_embeddings=config.max_position_embeddings,
                rope_theta=config.rope_theta))
            self.register_buffer("rope_cos", cos, persistable=False)
            self.register_buffer("rope_sin", sin, persistable=False)

    def forward(self, u, kv_cache=None, paged=None):
        cfg = self.config
        b, s, _ = u.shape
        h, kvh, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = self.q_proj(u).reshape(b, s, h, d)
        k = self.k_proj(u).reshape(b, s, kvh, d)
        v = self.v_proj(u).reshape(b, s, kvh, d)
        if paged is None:
            pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        else:
            tables, seq_lens, active = paged[:3]
            n_live = paged[3] if len(paged) > 3 else None
            pos = jnp.broadcast_to(
                seq_lens[:, None] + jnp.arange(s)[None, :], (b, s))
        if cfg.attention_rope:
            q = apply_rotary_pos_emb(q, self.rope_cos, self.rope_sin, pos)
            k = apply_rotary_pos_emb(k, self.rope_cos, self.rope_sin, pos)
        if paged is None:
            with jax.named_scope("core"):
                out = F.scaled_dot_product_attention(
                    q, jnp.repeat(k, h // kvh, axis=2),
                    jnp.repeat(v, h // kvh, axis=2), is_causal=True,
                    training=False)
            return self.o_proj(out.reshape(b, s, h * d))
        out, new_cache = F.paged_attention_write_attend(
            q, k, v, kv_cache, tables, seq_lens, pos, active, n_live,
            scale=1.0 / math.sqrt(d))
        return self.o_proj(out.reshape(b, s, h * d)), new_cache


class NemotronHBlock(Layer):
    def __init__(self, config: NemotronHConfig, kind: str):
        super().__init__(dtype=config.dtype)
        self.kind = kind
        self.norm = nn.RMSNorm(config.hidden_size, config.layer_norm_epsilon)
        if kind == MAMBA:
            self.mixer = NemotronHMamba2(config)
        elif kind == ATTENTION:
            self.mixer = NemotronHAttention(config)
        else:
            c = config
            self.mixer = HeldExpertsMoE(
                c.hidden_size, c.moe_latent_size, c.moe_intermediate_size,
                c.n_routed_experts, c.num_experts_per_tok,
                experts_held=c.experts_held,
                d_shared=(c.n_shared_experts
                          * c.moe_shared_expert_intermediate_size),
                activation=relu2, gated=False,
                norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor)


class NemotronHModel(Layer):
    @scoped_dtype_init
    def __init__(self, config: NemotronHConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([
            NemotronHBlock(config, kind)
            for kind in config.hybrid_override_pattern])
        self.norm_f = nn.RMSNorm(config.hidden_size,
                                 config.layer_norm_epsilon)


class NemotronHForCausalLM(Layer):
    @scoped_dtype_init
    def __init__(self, config: NemotronHConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = NemotronHModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, attn_mask=None, cache=None,
                position_offset=0, paged=None, lora=None):
        """Cache-free: ``forward(input_ids)`` -> logits [b, s, vocab].
        Served: ``forward(toks, None, cache, 0, (tables, seq_lens,
        active[, n_live]))`` -> (logits, the new ``HybridCache``, whose
        ``counts`` are the step's expert counters summed over the MoE
        layers)."""
        if attn_mask is not None or lora is not None:
            raise NotImplementedError(
                "NemotronHForCausalLM takes no attention mask and no LoRA")
        m = self.model
        with jax.named_scope("embed"):
            x = m.embed_tokens(input_ids)
        b, k = input_ids.shape
        live = fresh = None
        kv, states, counts = [], [], jnp.zeros((3,), jnp.int32)
        if cache is not None:
            _, seq_lens, active = paged[:3]
            n_live = paged[3] if len(paged) > 3 else k
            live = active[:, None] & (jnp.arange(k)[None, :]
                                      < jnp.reshape(n_live, (-1, 1)))
            fresh = fresh_slots(seq_lens, active)
            kv_in, state_in = iter(cache.kv), iter(cache.state)
        for block in m.layers:
            with jax.named_scope("norm"):
                u = block.norm(x)
            if block.kind == MAMBA:
                with jax.named_scope("ssm"):
                    if cache is None:
                        out = block.mixer(u)
                    else:
                        out, st = block.mixer(u, next(state_in), live, fresh)
                        states.append(st)
            elif block.kind == ATTENTION:
                with jax.named_scope("attn"):
                    if cache is None:
                        out = block.mixer(u)
                    else:
                        out, pair = block.mixer(u, next(kv_in), paged)
                        kv.append(pair)
            else:
                with jax.named_scope("moe"):
                    out, c = block.mixer(u, live)
                    counts = counts + c
            x = x + out
        with jax.named_scope("norm"):
            x = m.norm_f(x)
        with jax.named_scope("lm_head"):
            logits = (x @ m.embed_tokens.weight.T
                      if self.config.tie_word_embeddings
                      else self.lm_head(x))
        if cache is None:
            return logits
        return logits, cache._replace(kv=kv, state=states, counts=counts)

    def num_params(self) -> int:
        return sum(int(v.size) for v in self.param_dict().values())


def nemotron_h_tiny(**kw) -> NemotronHConfig:
    """A toy of the family for CPU tests: two ``ME*`` periods, 8 experts
    of which 4 are held, lane-aligned attention heads."""
    base = dict(
        vocab_size=256, hidden_size=64, num_hidden_layers=6,
        hybrid_override_pattern="ME*ME*", num_attention_heads=2,
        num_key_value_heads=1, head_dim=128, mamba_num_heads=4,
        mamba_head_dim=16, ssm_state_size=16, n_groups=2,
        n_routed_experts=8, num_experts_per_tok=3, moe_latent_size=32,
        moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
        experts_held=(0, 4), max_position_embeddings=512, dtype="float32")
    base.update(kw)
    return NemotronHConfig(**base)
