"""Dense GQA decoder (Llama/Mistral layout): what the harness needs to
hand a configuration of this family to the program. The leaf names and
the [in, out] layout of a linear weight are the benchmark's own
definition (benchmarks/reference/llama_dense.py uses the same); the
program's state dict has to match them or ``set_state_dict`` refuses.
"""

from __future__ import annotations

REFERENCE = "llama_dense"


def param_shapes(cfg: dict) -> dict[str, tuple]:
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    shapes = {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,)}
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head.weight"] = (h, v)
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        shapes.update({
            p + "self_attn.q_proj.weight": (h, nq * d),
            p + "self_attn.k_proj.weight": (h, nkv * d),
            p + "self_attn.v_proj.weight": (h, nkv * d),
            p + "self_attn.o_proj.weight": (nq * d, h),
            p + "mlp.gate_proj.weight": (h, f),
            p + "mlp.up_proj.weight": (h, f),
            p + "mlp.down_proj.weight": (f, h),
            p + "input_layernorm.weight": (h,),
            p + "post_attention_layernorm.weight": (h,),
        })
    return shapes


def build_model(cfg: dict, weights: dict, **overrides):
    """The program's model for ``cfg``, built without materialising its
    own initial values, holding ``weights``."""
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if cfg["hidden_size"] // cfg["num_attention_heads"] != cfg["head_dim"]:
        raise ValueError("LlamaConfig derives head_dim as hidden/heads")
    if cfg.get("sliding_window") is not None:
        raise ValueError("the program has no sliding-window attention")
    lc = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"], mp_axis=None, fsdp_axis=None, **overrides)
    with pt.LazyGuard():
        model = LlamaForCausalLM(lc)
    missing, unexpected = model.set_state_dict(weights)
    missing = [k for k in missing if "rope_" not in k]
    if missing or unexpected:
        raise ValueError(f"program and benchmark disagree on the leaves: "
                         f"missing {missing}, unexpected {unexpected}")
    return model
