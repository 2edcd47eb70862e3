"""Nemotron-H hybrid decoder (Mamba-2 / attention / LatentMoE blocks by
a pattern string): what the harness needs to hand a configuration of
this family to the program. The leaf names and the [in, out] layout of a
linear weight are the benchmark's own definition
(benchmarks/reference/nemotron_h.py uses the same); the program's state
dict has to match them or ``set_state_dict`` refuses.

A configuration holds this chip's SHARE of each expert layer
(``experts_held`` = [first, count] of ``n_routed_experts``; the router
keeps its width) and of the vocabulary (``vocab_size`` rows).

Three leaves of a Mamba-2 mixer are DERIVED from the seeded noise
(``derive_leaf``), by the published initialisation: ``benchmarks/weights``
makes a one-dimensional leaf 1 + noise, which as ``A_log`` and
``dt_bias`` would give ``A = -e`` and ``dt = softplus(1)`` on every
head: a state that forgets a row in two steps, and a comparison that
could not see a stale or lost state. Nothing here imports the program
at module level: the reference uses ``param_shapes`` and ``derive_leaf``.
"""

from __future__ import annotations

import math

REFERENCE = "nemotron_h"
MAMBA, ATTENTION, MOE = "M", "*", "E"
NOISE_STD = 0.02            # benchmarks/weights.STD: a 1-D leaf is 1 + noise


def mamba_dims(cfg: dict) -> dict:
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"h": h, "p": p, "g": g, "n": n, "d_inner": h * p,
            "conv_dim": h * p + 2 * g * n}


def layer_shapes(cfg: dict, kind: str) -> dict[str, tuple]:
    """The leaves of one block of ``kind``, without the layer's prefix."""
    hid = cfg["hidden_size"]
    out = {"norm.weight": (hid,)}
    if kind == MAMBA:
        m = mamba_dims(cfg)
        out.update({
            "mixer.in_proj.weight": (hid, 2 * m["d_inner"] + 2 * m["g"]
                                     * m["n"] + m["h"]),
            "mixer.conv1d_weight": (m["conv_dim"], cfg["conv_kernel"]),
            "mixer.conv1d_bias": (m["conv_dim"],),
            "mixer.A_log": (m["h"],), "mixer.dt_bias": (m["h"],),
            "mixer.D": (m["h"],),
            "mixer.norm_weight": (m["d_inner"],),
            "mixer.out_proj.weight": (m["d_inner"], hid)})
    elif kind == ATTENTION:
        d = cfg["head_dim"]
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        out.update({
            "mixer.q_proj.weight": (hid, nq * d),
            "mixer.k_proj.weight": (hid, nkv * d),
            "mixer.v_proj.weight": (hid, nkv * d),
            "mixer.o_proj.weight": (nq * d, hid)})
    elif kind == MOE:
        lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
        fs = cfg["n_shared_experts"] * cfg["moe_shared_expert_intermediate_size"]
        held = cfg["experts_held"][1]
        out.update({
            "mixer.gate.weight": (hid, cfg["n_routed_experts"]),
            "mixer.e_score_correction_bias": (cfg["n_routed_experts"],),
            "mixer.fc1_latent_proj.weight": (hid, lat),
            "mixer.fc2_latent_proj.weight": (lat, hid),
            "mixer.experts.w_in": (held, lat, f),
            "mixer.experts.w_out": (held, f, lat),
            "mixer.shared_up.weight": (hid, fs),
            "mixer.shared_down.weight": (fs, hid)})
    else:
        raise ValueError(f"no layer kind {kind!r}")
    return out


def param_shapes(cfg: dict) -> dict[str, tuple]:
    pat = cfg["hybrid_override_pattern"]
    if len(pat) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    v, hid = cfg["vocab_size"], cfg["hidden_size"]
    shapes = {"model.embed_tokens.weight": (v, hid),
              "model.norm_f.weight": (hid,), "lm_head.weight": (hid, v)}
    for i, kind in enumerate(pat):
        for k, shp in layer_shapes(cfg, kind).items():
            shapes[f"model.layers.{i}.{k}"] = shp
    return shapes


def _gain(name: str, cfg: dict) -> float:
    gains = cfg.get("assumed", {}).get("leaf_gains") or {}
    return float(next((g for end, g in gains.items()
                       if name.endswith(end)), 1.0))


def derive_leaf(name: str, leaf, cfg: dict):
    """The leaf ``name`` as the model holds it, from the seeded leaf
    (``benchmarks.weights.make_leaf``, float32 here). Pure ``jax.numpy``;
    the program's build and the reference both call it.

    ``A_log``: log of ``A`` spread over 1-16 (the published
    ``A_init_range``), ``dt_bias``: the inverse softplus of a time step
    log-spread over ``time_step_min`` to ``time_step_max`` and not under
    ``time_step_floor``; each from its own leaf's noise through the
    normal distribution function, so that per-row decays ``exp(-dt A)``
    lie between 0.2 and 0.999. ``D`` and every other leaf: as seeded
    (``D`` is 1 + noise: the published 1, and a missed ``D`` shows),
    times the power of two that ``assumed.leaf_gains`` gives its name's
    ending, if any (exact in bfloat16; the toy configurations of the
    tests make their narrow experts audible with it, no cell uses it)."""
    import jax.numpy as jnp
    from jax.scipy.special import erf

    kind = name.rsplit(".", 1)[-1]
    if kind not in ("A_log", "dt_bias"):
        return leaf * _gain(name, cfg)
    u = 0.5 * (1.0 + erf((leaf.astype(jnp.float32) - 1.0)
                         / (NOISE_STD * math.sqrt(2.0))))
    if kind == "A_log":
        return jnp.log(1.0 + 15.0 * u)
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    dt = jnp.maximum(jnp.exp(lo + u * (hi - lo)), cfg["time_step_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))


def build_model(cfg: dict, weights: dict, **overrides):
    """The program's model for ``cfg``, built without materialising its
    own initial values, holding ``weights`` (the derived leaves
    derived)."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)

    if cfg["tie_word_embeddings"] or cfg["attention_bias"] or cfg["mlp_bias"]:
        raise ValueError("the benchmark's leaves have no bias and an "
                         "untied head")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the program routes without expert groups")
    nc = NemotronHConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        hybrid_override_pattern=cfg["hybrid_override_pattern"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], attention_bias=cfg["attention_bias"],
        attention_rope=cfg["assumed"]["attention_rope"],
        rope_theta=cfg["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        use_conv_bias=cfg["use_conv_bias"],
        mamba_proj_bias=cfg["mamba_proj_bias"],
        n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_latent_size=cfg["moe_latent_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        experts_held=tuple(cfg["experts_held"]),
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        ssm_state_dtype=cfg["assumed"]["ssm_state_dtype"], **overrides)
    with pt.LazyGuard():
        model = NemotronHForCausalLM(nc)
    weights = {k: derive_leaf(k, v.astype(jnp.float32), cfg).astype(
                   jnp.float32 if k.endswith(("A_log", "dt_bias"))
                   else v.dtype)
               if k.endswith(("A_log", "dt_bias")) or _gain(k, cfg) != 1.0
               else v for k, v in weights.items()}
    missing, unexpected = model.set_state_dict(weights)
    missing = [k for k in missing if "rope_" not in k]
    if missing or unexpected:
        raise ValueError(f"program and benchmark disagree on the leaves: "
                         f"missing {missing}, unexpected {unexpected}")
    return model
