"""Builder `model`: the Llama family (MHA and GQA decoders through
`paddle_tpu.models.llama`). A configuration file selects it with
`"builder": "model"`; everything that is this family's lives here
(README, "A builder"). Widths are the file's; --rehearse swaps in a tiny
model of the same family shape (GQA ratio kept) for the CPU."""

#: --rehearse only: head_dim 64 is a width the kernel predicates accept
TINY = {"hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
        "num_attention_heads": 4, "head_dim": 64, "vocab_size": 512}


def load_config(raw, rehearse=False):
    """The configuration as it is run, from the file's parsed JSON."""
    cfg = dict(raw)
    if rehearse:
        group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
        cfg.update(TINY)
        cfg["num_key_value_heads"] = max(1, TINY["num_attention_heads"] // group)
    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError(f"{cfg.get('source')}: the program's decoder "
                         "derives head_dim as hidden_size / "
                         "num_attention_heads")
    return cfg


def build(cfg, seed, train, max_len, rehearse=False, recompute=False):
    """paddle.seed(seed), then the program's own construction: f32 on the
    device, cast to the configuration's bf16 (chip_smoke._build_model)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(int(seed) % (2 ** 31 - 1))
    bf16 = cfg.get("torch_dtype") == "bfloat16" and not rehearse
    lc = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=max_len,
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        use_recompute=recompute, fuse_linear_cross_entropy=train,
        dtype="bfloat16" if bf16 else "float32")
    model = LlamaForCausalLM(lc)
    if bf16:
        model.bfloat16()
    if not train:
        model.eval()
    return model


def criterion():
    """The training loss the step is built around: (logits or fused
    output, labels) -> scalar."""
    from paddle_tpu.models.llama import LlamaPretrainingCriterion

    return LlamaPretrainingCriterion()
