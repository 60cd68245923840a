"""Builder `phi4flash_model`: Phi-4-mini-flash
(`paddle_tpu.models.phi4flash`), whole: every layer, head and row of the
vocabulary as published. A configuration file selects it with `"builder":
"phi4flash_model"`.

The file's top-level keys are the source's own; what the source's
`config.json` does not say (Mamba's sizes, differential attention, the seeded
init) is listed under `assumed`. Every parameter is created in the
configuration's dtype: 3.85 B parameters built in float32 and cast would be
15.4 GB."""

#: --rehearse only: the program's tiny preset (eight layers, so that every
#: one of the six kinds of layer occurs), a window of two pages of 16
TINY = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 8,
        "num_attention_heads": 8, "num_key_value_heads": 4,
        "sliding_window": 32, "vocab_size": 512}

#: the source's keys the program's configuration class takes under their
#: own names
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "mb_per_layer",
    "sliding_window", "layer_norm_eps", "hidden_act", "tie_word_embeddings",
    "mlp_bias", "lm_head_bias", "embd_pdrop", "resid_pdrop", "mamba_d_state",
    "mamba_d_conv", "mamba_expand", "mamba_dt_rank")


def load_config(raw, rehearse=False):
    """The configuration as it is run, from the file's parsed JSON."""
    cfg = dict(raw)
    if rehearse:
        cfg.update(TINY)
    if cfg["hidden_size"] % cfg["num_attention_heads"] \
            or cfg["num_hidden_layers"] % 4 or cfg["mb_per_layer"] != 2:
        raise ValueError(f"{cfg.get('source')}: heads do not divide "
                         "hidden_size, or the depth is no multiple of 4 at "
                         "mb_per_layer 2 (the published layer table)")
    cfg["head_dim"] = cfg["hidden_size"] // cfg["num_attention_heads"]
    cfg["d_inner"] = cfg["mamba_expand"] * cfg["hidden_size"]
    return cfg


def build(cfg, seed, train, max_len, rehearse=False, recompute=False):
    """paddle.seed(seed), then the program's own construction (its seeded
    init is the configuration file's `assumed`) in the configuration's
    dtype. The model carries `benchmark_cfg` for the reference's
    comparison."""
    import paddle_tpu as paddle
    from paddle_tpu.models.phi4flash import (
        Phi4FlashConfig, Phi4FlashForCausalLM,
    )

    if train:
        raise ValueError("this decoder is served, not trained: it keeps no "
                         "tape and its scan has no backward")
    paddle.seed(int(seed) % (2 ** 31 - 1))
    bf16 = cfg.get("torch_dtype") == "bfloat16" and not rehearse
    model = Phi4FlashForCausalLM(Phi4FlashConfig(
        **{k: cfg[k] for k in MODEL_KEYS}, max_position_embeddings=max_len,
        dtype="bfloat16" if bf16 else "float32"))
    model.eval()
    model.benchmark_cfg = cfg
    return model
