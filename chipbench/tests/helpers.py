"""Small configurations of the benchmark's families for CPU tests."""
from repro.configs import get_config

ARCH = {"dense": "stablelm-1.6b", "moe": "granite-moe-1b-a400m"}


def smoke(family: str, dtype: str = "float32"):
    """(program ArchConfig, configuration dict as the references read it)
    of a two-layer model of the family."""
    cfg = get_config(ARCH[family], smoke=True).replace(
        param_dtype=dtype, compute_dtype=dtype)
    config = {"arch": ARCH[family], "family": family,
              "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
              "num_attention_heads": cfg.n_heads,
              "num_key_value_heads": cfg.kv_heads,
              "head_dim": cfg.head_dim, "num_hidden_layers": cfg.layers,
              "vocab_size": cfg.vocab, "rope_theta": cfg.rope_theta,
              "norm": cfg.norm, "layer_norm_eps": 1e-6, "rms_norm_eps": 1e-6,
              "param_dtype": dtype, "compute_dtype": dtype}
    if family == "moe":
        config.update(num_local_experts=cfg.n_experts,
                      num_experts_per_tok=cfg.top_k)
    return cfg, config
