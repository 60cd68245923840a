"""Builder `kimi_model`: the DeepSeek-V3 block that Kimi-K2 publishes
(`paddle_tpu.models.deepseek_v3`), as one chip's share of an expert-parallel
deployment. A configuration file selects it with `"builder": "kimi_model"`.

The file's top-level keys are the source's own. Two of them count what is
HELD here and not what is published: `n_routed_experts` (the experts of each
layer this chip holds, from `first_expert` on) and `vocab_size` (this chip's
slice of the vocabulary). The router keeps its published width,
`published.n_routed_experts`, and its experts per token. Every parameter is
created in the configuration's dtype: 4.85 B parameters built in float32 and
cast would be 19.4 GB."""

#: --rehearse only: two heads' worth of every width, 16 experts of which 4
#: are held, top 4, YaRN factor 4 over an original context of 32
TINY = {"hidden_size": 128, "intermediate_size": 256,
        "moe_intermediate_size": 64, "num_hidden_layers": 3,
        "num_attention_heads": 2, "q_lora_rank": 48, "kv_lora_rank": 32,
        "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
        "n_routed_experts": 4, "num_experts_per_tok": 4, "vocab_size": 512,
        "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                         "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 32}}
TINY_ROUTER_WIDTH = 16

#: the selection bias of a trained checkpoint is not zero; seeded so that the
#: choice (score + bias) and the weight (score alone) differ in every run
BIAS_STD = 0.05


def load_config(raw, rehearse=False):
    """The configuration as it is run, from the file's parsed JSON, with
    `router_width` (the published number of routed experts) beside the
    number held."""
    cfg = dict(raw)
    cfg["router_width"] = raw["published"]["n_routed_experts"]
    if rehearse:
        cfg.update(TINY)
        cfg["router_width"] = TINY_ROUTER_WIDTH
    held, first = cfg["n_routed_experts"], cfg.get("first_expert", 0)
    if not 0 <= first <= first + held <= cfg["router_width"]:
        raise ValueError(f"{cfg.get('source')}: experts [{first}, "
                         f"{first + held}) are no share of "
                         f"{cfg['router_width']}")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the program's gate has no group-limited routing")
    return cfg


def model_config(cfg, max_len, dtype):
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config

    return DeepseekV3Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_routed_experts=cfg["router_width"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        scoring_func=cfg["scoring_func"],
        norm_topk_prob=cfg["norm_topk_prob"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], rope_scaling=cfg["rope_scaling"],
        max_position_embeddings=max_len,
        tie_word_embeddings=cfg["tie_word_embeddings"],
        first_expert=cfg.get("first_expert", 0),
        n_held_experts=cfg["n_routed_experts"], dtype=dtype)


def build(cfg, seed, train, max_len, rehearse=False, recompute=False):
    """paddle.seed(seed), then the program's own construction in the
    configuration's dtype; the gates' selection biases seeded non-zero. The
    model carries `benchmark_cfg` for the reference's comparison."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework.core import Tensor
    from paddle_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM
    from paddle_tpu.nn import initializer as I

    if train:
        raise ValueError("the latent-attention decoder is served, not "
                         "trained: it keeps no tape")
    paddle.seed(int(seed) % (2 ** 31 - 1))
    bf16 = cfg.get("torch_dtype") == "bfloat16" and not rehearse
    model = DeepseekV3ForCausalLM(
        model_config(cfg, max_len, "bfloat16" if bf16 else "float32"))
    for name, p in model.state_dict().items():
        if name.endswith("e_score_correction_bias"):
            p.set_value(Tensor(I.Normal(0.0, BIAS_STD)(tuple(p.shape),
                                                        jnp.float32)))
    model.eval()
    model.benchmark_cfg = cfg
    return model
