"""Jamba hybrid decoder (Mamba-1 mixers, an attention mixer every
``attn_layer_period`` layers, a dense SwiGLU feed-forward in every
block, a tied head): what the harness needs to hand a configuration of
this family to the program. The leaf names and the [in, out] layout of a
linear weight are the benchmark's own definition
(benchmarks/reference/jamba.py uses the same); the program's state dict
has to match them or ``set_state_dict`` refuses.

Three leaves of a Mamba-1 mixer are DERIVED from the seeded noise
(``derive_leaf``), by Mamba's published initialisation, for the reason
``families/nemotron_h.py`` gives: ``benchmarks/weights`` makes a
one-dimensional leaf 1 + noise and a matrix plain noise, which as
``dt_proj.bias`` and ``A_log`` would give ``dt = softplus(1)`` and ``A =
-1`` on every channel: a state that forgets a row in two steps, and a
comparison that could not see a stale or lost state. Nothing here
imports the program at module level: the reference uses ``param_shapes``
and ``derive_leaf``.
"""

from __future__ import annotations

import math

REFERENCE = "jamba"
MAMBA, ATTENTION = "M", "*"
NOISE_STD = 0.02            # benchmarks/weights.STD: a 1-D leaf is 1 + noise
# Mamba's published range of the initial time step (``dt_min``,
# ``dt_max``, ``dt_init_floor`` of ``mamba_ssm``'s ``Mamba``); a Jamba
# config.json has no such keys
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


def layer_kinds(cfg: dict) -> str:
    """One letter a layer: ``*`` where ``i % attn_layer_period ==
    attn_layer_offset``, else ``M`` (the family's rule; the catalog
    lists the order of the layer types as not given:
    ``assumed.layer_order``)."""
    if cfg["num_experts"] != 1:
        raise ValueError("the benchmark's leaves have no routed experts")
    return "".join(
        ATTENTION if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
        else MAMBA for i in range(cfg["num_hidden_layers"]))


def mamba_dims(cfg: dict) -> dict:
    return {"d": cfg["mamba_expand"] * cfg["hidden_size"],
            "n": cfg["mamba_d_state"], "r": cfg["mamba_dt_rank"],
            "w": cfg["mamba_d_conv"]}


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_shapes(cfg: dict, kind: str) -> dict[str, tuple]:
    """The leaves of one block of ``kind``, without the layer's prefix."""
    hid, f = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"input_layernorm.weight": (hid,),
           "pre_ff_layernorm.weight": (hid,),
           "feed_forward.gate_proj.weight": (hid, f),
           "feed_forward.up_proj.weight": (hid, f),
           "feed_forward.down_proj.weight": (f, hid)}
    if kind == MAMBA:
        m = mamba_dims(cfg)
        d, n, r = m["d"], m["n"], m["r"]
        out.update({
            "mamba.in_proj.weight": (hid, 2 * d),
            "mamba.conv1d_weight": (d, m["w"]),
            "mamba.conv1d_bias": (d,),
            "mamba.x_proj.weight": (d, r + 2 * n),
            "mamba.dt_layernorm.weight": (r,),
            "mamba.b_layernorm.weight": (n,),
            "mamba.c_layernorm.weight": (n,),
            "mamba.dt_proj.weight": (r, d), "mamba.dt_proj.bias": (d,),
            "mamba.A_log": (d, n), "mamba.D": (d,),
            "mamba.out_proj.weight": (d, hid)})
    elif kind == ATTENTION:
        dh = head_dim(cfg)
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        out.update({
            "self_attn.q_proj.weight": (hid, nq * dh),
            "self_attn.k_proj.weight": (hid, nkv * dh),
            "self_attn.v_proj.weight": (hid, nkv * dh),
            "self_attn.o_proj.weight": (nq * dh, hid)})
    else:
        raise ValueError(f"no layer kind {kind!r}")
    return out


def param_shapes(cfg: dict) -> dict[str, tuple]:
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the benchmark's leaves have a tied head")
    if (not cfg["mamba_conv_bias"]) or cfg["mamba_proj_bias"]:
        raise ValueError("the benchmark's leaves have a conv bias and no "
                         "projection bias")
    shapes = {"model.embed_tokens.weight": (cfg["vocab_size"],
                                            cfg["hidden_size"]),
              "model.final_layernorm.weight": (cfg["hidden_size"],)}
    for i, kind in enumerate(layer_kinds(cfg)):
        for k, shp in layer_shapes(cfg, kind).items():
            shapes[f"model.layers.{i}.{k}"] = shp
    return shapes


DERIVED = ("A_log", "dt_proj.bias", "conv1d_weight")


def is_derived(name: str) -> bool:
    return name.endswith(DERIVED)


def derive_leaf(name: str, leaf, cfg: dict):
    """The leaf ``name`` as the model holds it, from the seeded leaf
    (``benchmarks.weights.make_leaf``, float32 here). Pure ``jax.numpy``;
    the program's build and the reference both call it.

    ``A_log[c, j] = log(j + 1)`` (S4D-real, Mamba's published
    initialisation: the seeded noise is not used). ``dt_proj.bias``: the
    inverse softplus of a time step log-spread over ``DT_MIN`` to
    ``DT_MAX`` and not under ``DT_FLOOR``, from the leaf's own noise
    through the normal distribution function, rounded to the
    configuration's dtype (a Linear's bias is stored in it); so per-row
    decays ``exp(-dt A)`` lie between 0.2 and 0.999 where ``dt_proj``'s
    input adds nothing. ``conv1d_weight``: the seeded noise times
    ``assumed.conv_gain`` (a power of two, exact in bfloat16: at std
    0.02 four taps add 0.04 to a bias of 1 and a lost or stale conv
    window could not be seen; PyTorch's default for a 4-tap depthwise
    conv is uniform on +-0.5, std 0.29). ``D`` and every other leaf: as
    seeded (``D`` is 1 + noise: the published 1, and a missed ``D``
    shows)."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.special import erf

    if name.endswith("conv1d_weight"):
        return leaf * float(cfg["assumed"]["conv_gain"])
    if name.endswith("A_log"):
        n = leaf.shape[-1]
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), leaf.shape)
    if not name.endswith("dt_proj.bias"):
        return leaf
    u = 0.5 * (1.0 + erf((leaf.astype(jnp.float32) - 1.0)
                         / (NOISE_STD * math.sqrt(2.0))))
    lo, hi = math.log(DT_MIN), math.log(DT_MAX)
    dt = jnp.maximum(jnp.exp(lo + u * (hi - lo)), DT_FLOOR)
    info = jnp.finfo(jnp.dtype(cfg["torch_dtype"]))
    return jax.lax.reduce_precision(dt + jnp.log(-jnp.expm1(-dt)),
                                    exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def build_model(cfg: dict, weights: dict, **overrides):
    """The program's model for ``cfg``, built without materialising its
    own initial values, holding ``weights`` (the derived leaves
    derived)."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models.jamba import JambaConfig, JambaForCausalLM

    jc = JambaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        hidden_act=cfg["hidden_act"], rms_norm_eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        max_position_embeddings=cfg["max_position_embeddings"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        expert_layer_period=cfg["expert_layer_period"],
        expert_layer_offset=cfg["expert_layer_offset"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"],
        mamba_dt_rank=cfg["mamba_dt_rank"],
        mamba_conv_bias=cfg["mamba_conv_bias"],
        mamba_proj_bias=cfg["mamba_proj_bias"], dtype=cfg["torch_dtype"],
        ssm_state_dtype=cfg["assumed"]["ssm_state_dtype"], **overrides)
    with pt.LazyGuard():
        model = JambaForCausalLM(jc)
    weights = {k: derive_leaf(k, v.astype(jnp.float32), cfg).astype(
                   jnp.float32 if k.endswith("A_log") else v.dtype)
               if is_derived(k) else v for k, v in weights.items()}
    missing, unexpected = model.set_state_dict(weights)
    if missing or unexpected:
        raise ValueError(f"program and benchmark disagree on the leaves: "
                         f"missing {missing}, unexpected {unexpected}")
    return model
