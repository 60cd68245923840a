"""Serving path: KV-cache decode, weight-only int8, and AOT export.

    python examples/serve_generate.py

Demonstrates: bucketed-prompt jitted generate(), weight-only int8
quantization of a trained model, and the StableHLO load-and-serve artifact
(jit.save/jit.load TranslatedLayer).
"""
import tempfile

import numpy as np

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.nn.quant import quantize_for_inference


def main():
    paddle.seed(0)
    cfg = llama_tiny(hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
                     intermediate_size=256, vocab_size=512)
    model = LlamaForCausalLM(cfg)
    model.eval()

    ids = np.random.RandomState(0).randint(0, 512, (2, 11)).astype(np.int32)
    out = model.generate(ids, max_new_tokens=8)
    print("generate:", out.shape, out.numpy()[0, -8:])

    # weight-only int8: same top-1 tokens, half the weight HBM traffic
    quantize_for_inference(model, "int8", skip=lambda n, l: "lm_head" in n)
    out8 = model.generate(ids, max_new_tokens=8)
    print("int8 generate:", out8.numpy()[0, -8:])

    # load-and-serve artifact (no Python class needed at load site)
    from paddle_tpu.static import InputSpec

    plain = LlamaForCausalLM(cfg)
    path = tempfile.mkdtemp() + "/llama"
    paddle.jit.save(plain, path, input_spec=[InputSpec([None, 16], "int32")])
    served = paddle.jit.load(path)
    logits = served(paddle.to_tensor(np.pad(ids, ((0, 0), (0, 5)))))
    print("TranslatedLayer logits:", logits.shape)

    # continuous batching over the paged KV pool: mixed-length requests
    # queue, join mid-flight as pages free, each result equals its dense
    # generate(); kv_cache_dtype="int8" halves the pool's HBM bytes
    from paddle_tpu.inference import ContinuousBatchingEngine

    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 512, (n,)).astype(np.int32) for n in (5, 13, 9, 21)]
    eng = ContinuousBatchingEngine(model, max_seqs=2, page_size=16,
                                   max_len=64, kv_cache_dtype="int8")
    outs = eng.serve(prompts, max_new_tokens=6, do_sample=True,
                     temperature=0.8, seed=0)
    print("continuous batching:", [len(o) for o in outs],
          f"pool={eng.pool_bytes() / 1e6:.2f}MB",
          f"decode_steps={eng.stats['decode_steps']}")


if __name__ == "__main__":
    main()
