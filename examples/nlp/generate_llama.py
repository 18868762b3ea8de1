"""Text generation with the KV-cache decoder (models/llama_decode.py).

Loads a transformers Llama checkpoint (or random-inits a preset), then
greedy- or sample-decodes.  With --hf-import and a tokenizer directory
this is an end-to-end "chat with the checkpoint" demo; without it, a
shape/throughput smoke.

Usage:
  python examples/nlp/generate_llama.py --model llama-7b --layers 2 \
      --hidden 64 --vocab 128 --max-new 16
  python examples/nlp/generate_llama.py --hf-import /path/to/llama \
      --prompt "The capital of France is" --max-new 32 --temperature 0.7
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import (LlamaConfig, LlamaForCausalLM, LLAMA_CONFIGS,
                             load_hf_llama_weights)
from hetu_tpu.models.llama_decode import greedy_generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-7b",
                    choices=list(LLAMA_CONFIGS))
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--hidden", type=int, default=0)
    ap.add_argument("--intermediate", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=0)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hf-import", default=None)
    ap.add_argument("--prompt", default=None,
                    help="text prompt (requires --hf-import with a "
                         "tokenizer)")
    args = ap.parse_args()

    base = dict(LLAMA_CONFIGS[args.model])
    for field, val in (("num_layers", args.layers),
                       ("hidden_size", args.hidden),
                       ("intermediate_size", args.intermediate),
                       ("vocab_size", args.vocab)):
        if val:
            base[field] = val
    c = LlamaConfig(seq_len=args.prompt_len + args.max_new, **base)

    model = LlamaForCausalLM(c, name="gen")
    ids = ht.placeholder_op("gen_ids", (1, args.prompt_len),
                            dtype=np.int32)
    ex = ht.Executor([model(ids)], seed=args.seed)

    tok = None
    if args.hf_import:
        import transformers
        hf = transformers.AutoModelForCausalLM.from_pretrained(
            args.hf_import)
        load_hf_llama_weights(ex, model, hf.state_dict(), name="gen")
        tok = transformers.AutoTokenizer.from_pretrained(args.hf_import)

    if args.prompt and tok is not None:
        prompt = np.asarray(tok(args.prompt)["input_ids"],
                            np.int32)[None, :]
    else:
        prompt = np.random.default_rng(args.seed).integers(
            1, c.vocab_size, (1, args.prompt_len)).astype(np.int32)

    import time
    import jax
    import jax.numpy as jnp
    from hetu_tpu.models.llama_decode import build_greedy_decode
    moe_names = None
    if c.num_experts:
        moe_names = [{"wg": l.mlp.gate.wg.name, "w1": l.mlp.w1.name,
                      "w2": l.mlp.w2.name, "w3": l.mlp.w3.name}
                     for l in model.model.layers]
    fn = build_greedy_decode(c, args.max_new, name="gen",
                             temperature=args.temperature,
                             top_k=args.top_k, moe_names=moe_names)
    key = jax.random.key(args.seed)
    pids = jnp.asarray(prompt, jnp.int32)
    out = np.asarray(fn(ex.params, pids, key))   # compile
    t0 = time.perf_counter()
    out = np.asarray(fn(ex.params, pids, key))
    dt = time.perf_counter() - t0
    new = out[0, prompt.shape[1]:]
    print(f"{args.max_new} tokens in {dt*1e3:.1f} ms "
          f"({args.max_new/dt:.1f} tok/s, cached decode)")
    if tok is not None:
        print(tok.decode(out[0].tolist()))
    else:
        print("generated ids:", new.tolist())


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
