"""openPangu-Ultra-MoE decoder (``pangu_ultra_moe``: multi-head latent
attention, four norms a block, a gated dense MLP in the leading layers
and 256 gated routed experts beside one shared expert after them): what
the harness needs to hand a configuration of this family to the program.
The leaf names and the [in, out] layout of a linear weight are the
benchmark's own definition (benchmarks/reference/pangu_moe.py uses the
same); the program's state dict has to match them or ``set_state_dict``
refuses.

A configuration holds this chip's SHARE of each expert layer
(``experts_held`` = [first, count] of ``n_routed_experts``; the router
keeps its width) and of the vocabulary (``vocab_size`` rows). The three
things the published config does not say are booleans under ``assumed``
(``router_score_bias``, ``rope_interleave``, ``post_norm_on_output``),
handed to the program's config and read by the reference.

``hybrid_override_pattern`` is the harness's own note of the layer
kinds, one letter a layer (``-`` a dense-MLP layer, ``E`` an expert
layer): ``layer_metrics/experts_touched_share.py`` counts a
configuration's expert layers from that key, whatever the family.
``param_shapes`` refuses a file whose pattern and published keys
disagree. Nothing here imports the program at module level.
"""

from __future__ import annotations

REFERENCE = "pangu_moe"
DENSE, MOE = "-", "E"


def layer_kinds(cfg: dict) -> str:
    n, k = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    pat = DENSE * k + MOE * (n - k)
    if cfg.get("hybrid_override_pattern", pat) != pat:
        raise ValueError(
            f"hybrid_override_pattern {cfg['hybrid_override_pattern']!r} "
            f"disagrees with first_k_dense_replace {k} of {n} layers: {pat!r}")
    return pat


def attention_shapes(cfg: dict) -> dict[str, tuple]:
    hid, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return {"self_attn.q_a_proj.weight": (hid, ql),
            "self_attn.q_a_layernorm.weight": (ql,),
            "self_attn.q_b_proj.weight": (ql, h * (dn + dr)),
            "self_attn.kv_a_proj_with_mqa.weight": (hid, kvl + dr),
            "self_attn.kv_a_layernorm.weight": (kvl,),
            "self_attn.kv_b_proj.weight": (kvl, h * (dn + dv)),
            "self_attn.o_proj.weight": (h * dv, hid)}


def layer_shapes(cfg: dict, kind: str) -> dict[str, tuple]:
    """The leaves of one block of ``kind``, without the layer's prefix."""
    hid = cfg["hidden_size"]
    out = {f"{n}.weight": (hid,) for n in (
        "input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
        "post_mlp_layernorm")}
    out.update(attention_shapes(cfg))
    if kind == DENSE:
        f = cfg["intermediate_size"]
        out.update({"mlp.gate_proj.weight": (hid, f),
                    "mlp.up_proj.weight": (hid, f),
                    "mlp.down_proj.weight": (f, hid)})
    elif kind == MOE:
        f, held = cfg["moe_intermediate_size"], cfg["experts_held"][1]
        fs = cfg["n_shared_experts"] * f
        out.update({"mlp.gate.weight": (hid, cfg["n_routed_experts"]),
                    "mlp.experts.w_gate": (held, hid, f),
                    "mlp.experts.w_in": (held, hid, f),
                    "mlp.experts.w_out": (held, f, hid),
                    "mlp.shared_gate.weight": (hid, fs),
                    "mlp.shared_up.weight": (hid, fs),
                    "mlp.shared_down.weight": (fs, hid)})
        if cfg["assumed"]["router_score_bias"]:
            out["mlp.e_score_correction_bias"] = (cfg["n_routed_experts"],)
    else:
        raise ValueError(f"no layer kind {kind!r}")
    return out


def param_shapes(cfg: dict) -> dict[str, tuple]:
    v, hid = cfg["vocab_size"], cfg["hidden_size"]
    shapes = {"model.embed_tokens.weight": (v, hid),
              "model.norm.weight": (hid,), "lm_head.weight": (hid, v)}
    for i, kind in enumerate(layer_kinds(cfg)):
        for k, shp in layer_shapes(cfg, kind).items():
            shapes[f"model.layers.{i}.{k}"] = shp
    return shapes


def build_model(cfg: dict, weights: dict, **overrides):
    """The program's model for ``cfg``, built without materialising its
    own initial values, holding ``weights``."""
    import paddle_tpu as pt
    from paddle_tpu.models.pangu_moe import (PanguMoEConfig,
                                             PanguMoEForCausalLM)

    if cfg["tie_word_embeddings"] or cfg["attention_bias"]:
        raise ValueError("the benchmark's leaves have no bias and an "
                         "untied head")
    if cfg["hidden_act"] != "silu" or not cfg["sandwich_norm"]:
        raise ValueError("the program's block is the sandwich-norm block "
                         "with SiLU gates")
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("latent attention has one latent for every head")
    a = cfg["assumed"]
    pc = PanguMoEConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        intermediate_size=cfg["intermediate_size"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rope_interleave=a["rope_interleave"],
        n_routed_experts=cfg["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        router_score_bias=a["router_score_bias"],
        experts_held=tuple(cfg["experts_held"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        post_norm_on_output=a["post_norm_on_output"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], **overrides)
    with pt.LazyGuard():
        model = PanguMoEForCausalLM(pc)
    missing, unexpected = model.set_state_dict(weights)
    if missing or unexpected:
        raise ValueError(f"program and benchmark disagree on the leaves: "
                         f"missing {missing}, unexpected {unexpected}")
    return model
