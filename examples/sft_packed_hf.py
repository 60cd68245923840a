"""SFT-style fine-tuning demo: import a HuggingFace LLaMA checkpoint,
pack ragged conversations into fixed rows with segment_ids (within-segment
causal attention, rope restarting per segment — splash SegmentIds kernel
on TPU), train, then serve the result through the continuous-batching
paged engine.

    JAX_PLATFORMS=cpu python examples/sft_packed_hf.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.models.llama import LlamaPretrainingCriterion


def main():
    paddle.seed(0)
    rng = np.random.RandomState(0)

    # 1) import a (toy) HF checkpoint — exact-parity conversion
    try:
        import torch
        from transformers import LlamaConfig as HFConfig
        from transformers import LlamaForCausalLM as HFLlama

        from paddle_tpu.models import hf_compat

        torch.manual_seed(0)
        hf = HFLlama(HFConfig(vocab_size=256, hidden_size=64,
                              intermediate_size=128, num_hidden_layers=2,
                              num_attention_heads=4, num_key_value_heads=2,
                              max_position_embeddings=128,
                              attn_implementation="eager"))
        model = hf_compat.from_hf(hf)
        print("imported HF checkpoint:", model.num_parameters(), "params")
    except ImportError:  # torch/transformers absent: fresh weights
        from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

        model = LlamaForCausalLM(llama_tiny(vocab_size=256))
        print("transformers unavailable — fresh weights")

    V = model.config.vocab_size

    # 2) pack ragged "conversations" into [B, 32] rows with segment ids
    def pack_row(lengths):
        ids = np.concatenate([rng.randint(1, V, (l,)) for l in lengths])
        seg = np.concatenate([np.full(l, i) for i, l in enumerate(lengths)])
        labels = np.roll(ids, -1)
        labels[np.cumsum(lengths) - 1] = -100  # no prediction across joints
        return ids.astype(np.int32), seg.astype(np.int32), labels.astype(np.int32)

    rows = [pack_row([9, 14, 9]), pack_row([20, 12])]
    ids = paddle.to_tensor(np.stack([r[0] for r in rows]))
    seg = paddle.to_tensor(np.stack([r[1] for r in rows]))
    labels = paddle.to_tensor(np.stack([r[2] for r in rows]))

    opt = optimizer.AdamW(learning_rate=3e-3, parameters=model.parameters())
    for step in range(10):
        out = model(ids, segment_ids=seg)
        loss = LlamaPretrainingCriterion()(out, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        if step % 3 == 0:
            print(f"step {step}: loss {float(loss.numpy()):.4f}")

    # 3) serve the tuned model: continuous batching over the paged KV pool
    from paddle_tpu.inference import ContinuousBatchingEngine

    model.eval()
    prompts = [rng.randint(1, V, (n,)).astype(np.int32) for n in (6, 15, 11)]
    eng = ContinuousBatchingEngine(model, max_seqs=2, page_size=16, max_len=64)
    outs = eng.serve(prompts, max_new_tokens=8)
    print("served:", [len(o) for o in outs],
          f"pool={eng.pool_bytes() / 1e6:.2f}MB",
          f"decode_steps={eng.stats['decode_steps']}")


if __name__ == "__main__":
    main()
