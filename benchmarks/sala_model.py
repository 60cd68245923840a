"""Builder `sala_model`: MiniCPM-SALA (`paddle_tpu.models.minicpm_sala`), a
slice of the published layers at every published width. A configuration file
selects it with `"builder": "sala_model"`.

The file's top-level keys are the source's own; `num_hidden_layers` and
`mixer_types` are the slice held here, `published` holds the whole model's,
and the residual scale reads the PUBLISHED depth. `sparse_config` is the
family's (MiniCPM4), listed under `assumed`. Every parameter is created in
the configuration's dtype: 2.82 B parameters built in float32 and cast would
be 11.3 GB."""

#: --rehearse only: four heads' worth of every width, a slice of four layers
#: (one sparse, three lightning) of a published eight, and a sparse_config
#: shrunk with them (pages of 16 keys: the rehearsal's page_size)
TINY = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 4,
        "mixer_types": ["minicpm4"] + ["lightning-attn"] * 3,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 32,
        "vocab_size": 512, "dim_model_base": 32,
        "published": {"num_hidden_layers": 8,
                      "mixer_types": (["minicpm4"]
                                      + ["lightning-attn"] * 3) * 2},
        "sparse_config": {"kernel_size": 8, "kernel_stride": 4,
                          "block_size": 16, "topk": 4, "init_blocks": 1,
                          "window_size": 32, "dense_len": 64}}

KINDS = ("minicpm4", "lightning-attn")

#: the learned q/k norm scales of the minicpm4 layers, seeded so that a
#: score q . k / sqrt(d) has a standard deviation of 3 (1.732^2) instead of 1:
#: a trained model's attention is peaked; with unit scales the softmax over
#: ten thousand random keys is near uniform, the layer's output is 0.4% of
#: the residual stream, and no comparison could tell right pages from wrong
QK_NORM_SCALE = 1.732


def load_config(raw, rehearse=False):
    """The configuration as it is run, from the file's parsed JSON."""
    cfg = dict(raw)
    if rehearse:
        cfg.update(TINY)
    kinds = cfg["mixer_types"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(f"{cfg.get('source')}: mixer_types names "
                         f"{len(kinds)} layers of kinds {sorted(set(kinds))} "
                         f"for num_hidden_layers {cfg['num_hidden_layers']}")
    whole = cfg["published"]["mixer_types"]
    if not any(whole[i:i + len(kinds)] == kinds
               for i in range(len(whole) - len(kinds) + 1)):
        raise ValueError(f"{cfg.get('source')}: mixer_types is no contiguous "
                         "slice of published.mixer_types")
    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"] \
            or cfg["hidden_size"] != (cfg["lightning_nh"]
                                      * cfg["lightning_head_dim"]):
        raise ValueError(f"{cfg.get('source')}: hidden_size is not heads x "
                         "head_dim on both mixers")
    return cfg


#: the source's keys the program's configuration class takes under their
#: own names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "mixer_types", "num_attention_heads", "num_key_value_heads", "head_dim",
    "lightning_nh", "lightning_nkv", "lightning_head_dim", "lightning_scale",
    "lightning_use_rope", "attn_use_rope", "qk_norm", "use_output_gate",
    "use_output_norm", "attn_use_output_gate", "attention_bias",
    "hidden_act", "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth",
    "dim_model_base", "mup_denominator", "tie_word_embeddings",
    "sparse_config")


def model_config(cfg, max_len, dtype):
    from paddle_tpu.models.minicpm_sala import MinicpmSalaConfig

    return MinicpmSalaConfig(
        **{k: cfg[k] for k in MODEL_KEYS}, max_position_embeddings=max_len,
        residual_depth=cfg["published"]["num_hidden_layers"], dtype=dtype)


def build(cfg, seed, train, max_len, rehearse=False, recompute=False):
    """paddle.seed(seed), then the program's own construction in the
    configuration's dtype. The model carries `benchmark_cfg` for the
    reference's comparison."""
    import paddle_tpu as paddle
    from paddle_tpu.models.minicpm_sala import MinicpmSalaForCausalLM

    if train:
        raise ValueError("this decoder is served, not trained: it keeps no "
                         "tape and neither mixer has a backward")
    paddle.seed(int(seed) % (2 ** 31 - 1))
    bf16 = cfg.get("torch_dtype") == "bfloat16" and not rehearse
    model = MinicpmSalaForCausalLM(
        model_config(cfg, max_len, "bfloat16" if bf16 else "float32"))
    for layer in model.model.layers:
        if layer.mixer_type == "minicpm4":
            for norm in (layer.self_attn.q_norm, layer.self_attn.k_norm):
                norm.weight._data = norm.weight._data * QK_NORM_SCALE
    model.eval()
    model.benchmark_cfg = cfg
    return model
