"""Model configuration from a configuration file, shared by the runners."""

from __future__ import annotations

import dataclasses

# keys of the published configuration, against the program's config
HF_KEYS = (
    "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "num_hidden_layers", "num_local_experts", "num_experts_per_tok",
    "vocab_size", "rope_theta", "rms_norm_eps", "sliding_window", "tie_word_embeddings",
)


def model_config(conf: dict, tiny: bool):
    """The program's ``ModelConfig`` for this file (smoke widths with
    ``tiny``), and the published-style dict the reference reads."""
    from repro.configs import get_config, smoke_config

    base = smoke_config(conf["registry"]) if tiny else get_config(conf["registry"])
    cfg = dataclasses.replace(
        base,
        **conf["overrides"],
        moe=dataclasses.replace(base.moe, **conf["moe_overrides"]),
    )
    hf = published(cfg)
    if not tiny:
        wrong = {k: (hf[k], conf[k]) for k in HF_KEYS if hf[k] != conf[k]}
        if wrong:
            raise SystemExit(f"{conf['name']}: the program's config differs from the file: {wrong}")
    # the reference implements exactly this block
    m = cfg.moe
    if not (
        cfg.block == "attn" and cfg.hybrid is None and cfg.pos_embedding == "rope"
        and not cfg.qkv_bias and not cfg.ffn_gelu and cfg.frontend == "none"
        and m.every == 1 and m.router_norm_topk and not m.expert_2d and m.wire_dtype == "bf16"
    ):
        raise SystemExit(f"{conf['name']}: not the decoder block the reference implements")
    return cfg, hf


def published(cfg) -> dict:
    return {
        "hidden_size": cfg.d_model,
        "intermediate_size": cfg.moe.d_ff_expert,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.resolved_head_dim,
        "num_hidden_layers": cfg.n_layers,
        "num_local_experts": cfg.moe.n_experts,
        "num_experts_per_tok": cfg.moe.top_k,
        "vocab_size": cfg.vocab_size,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "sliding_window": cfg.sliding_window,
        "tie_word_embeddings": cfg.tie_embeddings,
    }
