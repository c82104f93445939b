from llm_d_tpu.models.config import ModelConfig, PRESETS, get_config


def get_model(config: ModelConfig):
    """Model module for a config: ``models.moe`` for MoE configs
    (num_experts > 0; linear-attention layers among its MLA layers
    included), ``models.hybrid_decoder`` for a stack whose ``layer_types``
    name mixers (state-space layers, gated memory units and cross-layer
    attention in place of a layer's own attention), ``models.ssm`` for a
    dense stack with a state-space mixer beside attention in every layer,
    ``models.llama`` for dense.  Each module exposes init_params / forward /
    compute_logits / sharding_rules / kv_cache_spec."""
    if config.mixer_by_layer:
        from llm_d_tpu.models import hybrid_decoder
        return hybrid_decoder
    if config.is_moe:
        from llm_d_tpu.models import moe
        return moe
    if config.has_recurrent_state:
        from llm_d_tpu.models import ssm
        return ssm
    from llm_d_tpu.models import llama
    return llama


__all__ = ["ModelConfig", "PRESETS", "get_config", "get_model"]
