"""Jamba hybrid decoder (``model_type`` ``jamba``; published description:
huggingface.co/ai21labs/AI21-Jamba2-3B, ``transformers``
``modeling_jamba.py``): a stack of pre-norm residual blocks, each a
mixer and a feed-forward,
``x = x + mixer_i(rmsnorm(x)); x = x + mlp_i(rmsnorm(x))``:

- layer ``i`` with ``i % attn_layer_period == attn_layer_offset`` mixes
  by attention: grouped- (here multi-) query, no bias and NO positional
  term of any kind (the Mamba layers carry position), through the same
  paged core as Llama's (``F.paged_attention_write_attend``);
- every other layer by a Mamba-1 mixer (``nn.functional.ssm``): in_proj
  to ``[x | z]``, a depthwise causal conv over the last ``mamba_d_conv``
  rows, ``x_proj`` to a low-rank time step and the row's ``B`` and
  ``C``, an RMS norm on each of the three (Jamba's addition to Mamba-1),
  ``dt_proj``, the selective scan with a decay for every channel and
  state index, the gate ``silu(z)``, out_proj;
- the feed-forward is Llama's SwiGLU MLP (``num_experts`` 1; the
  family's expert variant is refused by name).

The head is the embedding (``tie_word_embeddings``).

Serving only: ``forward(input_ids)`` is the cache-free pass the tests
compare with the reference, and ``forward(toks, None, cache, 0, paged)``
is the call ``ServingEngine``'s two step programs make. ``cache`` is a
``serving.kv_cache.HybridCache``: K/V page pairs for the attention
layers, a per-slot ``(conv window, SSM state)`` pair for each Mamba
layer, both in layer order. The SSM state is ``[slots, d_state,
d_inner]``: the channels fill the lanes. A slot that starts at position
0 starts from zero state (``fresh_slots``); a mixed step advances a
slot's state by its ``n_live`` rows and no more; an inactive slot's
state is untouched. No training path.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dtypes import scoped_dtype_init
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.functional.ssm import fresh_slots
from ..nn.module import Layer, Parameter
from .llama import LlamaConfig, LlamaMLP

__all__ = ["JambaConfig", "JambaForCausalLM", "JambaMambaMixer",
           "JambaAttention", "JambaExpertsError", "jamba_tiny"]


class JambaExpertsError(NotImplementedError):
    """A Jamba config with routed experts (``num_experts > 1``): the
    family's expert variant is not served."""


@dataclass
class JambaConfig:
    """The published keys of a ``jamba`` config.json (defaults:
    AI21-Jamba2-3B), plus ``head_dim`` (the config gives none: hidden
    size over heads) and what a serving slot keeps."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 262144
    # attention
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    # experts: every feed-forward is dense at num_experts 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    # Mamba-1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    dtype: str = "bfloat16"
    # state kept by a serving slot: the SSM state in float32, the conv
    # window in the model's dtype
    ssm_state_dtype: str = "float32"

    def __post_init__(self):
        if self.num_experts > 1:
            raise JambaExpertsError(
                f"num_experts={self.num_experts}: the Jamba family's "
                f"expert variant is not served (every feed-forward here "
                f"is the dense MLP)")
        if self.hidden_act != "silu":
            raise ValueError(f"hidden_act {self.hidden_act!r}: the "
                             f"feed-forward is SwiGLU")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def is_attention(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset

    def cache_layers(self) -> list:
        """What each layer keeps for a request being served, in layer
        order (``serving.kv_cache`` builds the pool from it): ``("pages",
        kv heads, head dim)`` or ``("state", ((shape, dtype), ...))``."""
        state = ("state", (
            ((self.mamba_d_conv - 1, self.mamba_d_inner), self.dtype),
            ((self.mamba_d_state, self.mamba_d_inner),
             self.ssm_state_dtype)))
        pages = ("pages", self.num_key_value_heads, self.head_dim)
        return [pages if self.is_attention(i) else state
                for i in range(self.num_hidden_layers)]


class JambaMambaMixer(Layer):
    def __init__(self, config: JambaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        c = config
        d, n, r = c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank
        bias = None if c.mamba_proj_bias else False
        self.in_proj = nn.Linear(c.hidden_size, 2 * d, bias_attr=bias)
        # depthwise causal conv, [channels, taps], the last tap on the
        # current row
        self.conv1d_weight = Parameter(I.XavierNormal()(
            (d, c.mamba_d_conv), self._dtype))
        self.conv1d_bias = (Parameter(I.Constant(0.0)((d,), self._dtype))
                            if c.mamba_conv_bias else None)
        self.x_proj = nn.Linear(d, r + 2 * n, bias_attr=False)
        self.dt_layernorm = nn.RMSNorm(r, c.rms_norm_eps)
        self.b_layernorm = nn.RMSNorm(n, c.rms_norm_eps)
        self.c_layernorm = nn.RMSNorm(n, c.rms_norm_eps)
        self.dt_proj = nn.Linear(r, d)
        self.A_log = Parameter(I.Constant(0.0)((d, n), jnp.float32))
        self.D = Parameter(I.Constant(1.0)((d,), jnp.float32))
        self.out_proj = nn.Linear(d, c.hidden_size, bias_attr=bias)

    def forward(self, u, state=None, n_live=None, fresh=None):
        """u [b, k, hidden]. ``state`` = (conv window [b, w - 1, d], SSM
        state [b, n, d]) or None (a sequence from its start); ``n_live``
        [b] the rows of each slot that count (0 for an inactive slot),
        ``fresh`` [b] the slots that start from zero. Returns the
        mixer's output, and the new state where one came."""
        c = self.config
        b, k, _ = u.shape
        d, n, r = c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank
        f32 = jnp.float32
        with jax.named_scope("in_proj"):
            x, z = jnp.split(self.in_proj(u), 2, axis=-1)
        if state is None:
            window = jnp.zeros((b, c.mamba_d_conv - 1, d), x.dtype)
            ssm = jnp.zeros((b, n, d), f32)
            n_live = jnp.full((b,), k, jnp.int32)
        else:
            window, ssm = state
            window = jnp.where(fresh[:, None, None], 0, window)
            ssm = jnp.where(fresh[:, None, None], 0, ssm)
        bias = (self.conv1d_bias if self.conv1d_bias is not None
                else jnp.zeros((d,), f32))
        with jax.named_scope("conv"):
            x, new_window = F.causal_conv1d_window(
                x, window, self.conv1d_weight, bias, n_live)
            x = jax.nn.silu(x).astype(u.dtype)
        with jax.named_scope("x_proj"):
            dt, B, C = jnp.split(self.x_proj(x), [r, r + n], axis=-1)
            B = self.b_layernorm(B).astype(f32)
            C = self.c_layernorm(C).astype(f32)
            dt = jax.nn.softplus(
                self.dt_proj(self.dt_layernorm(dt)).astype(f32))
        A = -jnp.exp(self.A_log)
        with jax.named_scope("scan"):
            if k == 1:
                # a dead row (an inactive slot): dt = 0, the state as it was
                y, new_ssm = F.selective_scan_step(
                    x[:, 0].astype(f32),
                    jnp.where(n_live[:, None] > 0, dt[:, 0], 0.0), A,
                    B[:, 0], C[:, 0], self.D, ssm.astype(f32))
                y = y[:, None]
            else:
                y, new_ssm = F.selective_scan_rows(
                    x, dt, A, B, C, self.D, ssm, n_live)
        with jax.named_scope("out_proj"):
            out = self.out_proj(
                (y * jax.nn.silu(z.astype(f32))).astype(u.dtype))
        if state is None:
            return out
        return out, (new_window, new_ssm.astype(state[1].dtype))


class JambaAttention(Layer):
    def __init__(self, config: JambaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        h, kvh, d = (config.num_attention_heads, config.num_key_value_heads,
                     config.head_dim)
        self.q_proj = nn.Linear(config.hidden_size, h * d, bias_attr=False)
        self.k_proj = nn.Linear(config.hidden_size, kvh * d, bias_attr=False)
        self.v_proj = nn.Linear(config.hidden_size, kvh * d, bias_attr=False)
        self.o_proj = nn.Linear(h * d, config.hidden_size, bias_attr=False)

    def forward(self, u, kv_cache=None, paged=None):
        cfg = self.config
        b, s, _ = u.shape
        h, kvh, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = self.q_proj(u).reshape(b, s, h, d)
        k = self.k_proj(u).reshape(b, s, kvh, d)
        v = self.v_proj(u).reshape(b, s, kvh, d)
        if paged is None:
            with jax.named_scope("core"):
                out = F.scaled_dot_product_attention(
                    q, jnp.repeat(k, h // kvh, axis=2),
                    jnp.repeat(v, h // kvh, axis=2), is_causal=True,
                    training=False)
            return self.o_proj(out.reshape(b, s, h * d))
        tables, seq_lens, active = paged[:3]
        n_live = paged[3] if len(paged) > 3 else None
        pos = jnp.broadcast_to(
            seq_lens[:, None] + jnp.arange(s)[None, :], (b, s))
        out, new_cache = F.paged_attention_write_attend(
            q, k, v, kv_cache, tables, seq_lens, pos, active, n_live,
            scale=1.0 / math.sqrt(d))
        return self.o_proj(out.reshape(b, s, h * d)), new_cache


class JambaBlock(Layer):
    def __init__(self, config: JambaConfig, attention: bool):
        super().__init__(dtype=config.dtype)
        self.attention = attention
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        if attention:
            self.self_attn = JambaAttention(config)
        else:
            self.mamba = JambaMambaMixer(config)
        self.pre_ff_layernorm = nn.RMSNorm(config.hidden_size,
                                           config.rms_norm_eps)
        self.feed_forward = LlamaMLP(LlamaConfig(
            hidden_size=config.hidden_size,
            intermediate_size=config.intermediate_size, dtype=config.dtype,
            mp_axis=None, fsdp_axis=None))


class JambaModel(Layer):
    @scoped_dtype_init
    def __init__(self, config: JambaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList([
            JambaBlock(config, config.is_attention(i))
            for i in range(config.num_hidden_layers)])
        self.final_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)


class JambaForCausalLM(Layer):
    @scoped_dtype_init
    def __init__(self, config: JambaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.model = JambaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, attn_mask=None, cache=None,
                position_offset=0, paged=None, lora=None):
        """Cache-free: ``forward(input_ids)`` -> logits [b, s, vocab].
        Served: ``forward(toks, None, cache, 0, (tables, seq_lens,
        active[, n_live]))`` -> (logits, the new ``HybridCache``; the
        model has no expert layer, so its ``counts`` stay zero)."""
        if attn_mask is not None or lora is not None:
            raise NotImplementedError(
                "JambaForCausalLM takes no attention mask and no LoRA")
        m = self.model
        with jax.named_scope("embed"):
            x = m.embed_tokens(input_ids)
        b, k = input_ids.shape
        n_live = fresh = None
        kv, states = [], []
        if cache is not None:
            _, seq_lens, active = paged[:3]
            n_live = jnp.where(
                active, paged[3] if len(paged) > 3 else k, 0
            ).astype(jnp.int32)
            fresh = fresh_slots(seq_lens, active)
            kv_in, state_in = iter(cache.kv), iter(cache.state)
        for block in m.layers:
            with jax.named_scope("norm"):
                u = block.input_layernorm(x)
            if block.attention:
                with jax.named_scope("attn"):
                    if cache is None:
                        out = block.self_attn(u)
                    else:
                        out, pair = block.self_attn(u, next(kv_in), paged)
                        kv.append(pair)
            else:
                with jax.named_scope("ssm"):
                    if cache is None:
                        out = block.mamba(u)
                    else:
                        out, st = block.mamba(u, next(state_in), n_live,
                                              fresh)
                        states.append(st)
            x = x + out
            with jax.named_scope("norm"):
                u = block.pre_ff_layernorm(x)
            with jax.named_scope("mlp"):
                x = x + block.feed_forward(u)
        with jax.named_scope("norm"):
            x = m.final_layernorm(x)
        with jax.named_scope("lm_head"):
            logits = (x @ m.embed_tokens.weight.T
                      if self.config.tie_word_embeddings
                      else self.lm_head(x))
        if cache is None:
            return logits
        return logits, cache._replace(
            kv=kv, state=states, counts=jnp.zeros((3,), jnp.int32))

    def num_params(self) -> int:
        return sum(int(v.size) for v in self.param_dict().values())


def jamba_tiny(**kw) -> JambaConfig:
    """A toy of the family for CPU tests: two periods of four layers
    (attention at layers 2 and 6), 2 query heads on ONE K/V head of 128,
    a lane-wide inner width."""
    base = dict(
        vocab_size=256, hidden_size=256, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=2, num_key_value_heads=1,
        attn_layer_period=4, attn_layer_offset=2, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=16,
        max_position_embeddings=512, dtype="float32")
    base.update(kw)
    return JambaConfig(**base)
