"""Llama/Baichuan causal-LM training (reference:
tools/Hetu-Galvatron/galvatron/models/llama/train.py, models/baichuan/).

Covers the graph-API training path with optional parallelism flags:
  --tp/--dp      dp x tp via the MegatronLM strategy (SwiGLU gate/up
                 column-parallel, down row-parallel)
  --pp           graph-pipeline staging (1f1b schedule)
  --hf-import    load a transformers Llama checkpoint by path

Usage: python examples/nlp/train_llama.py [--model llama-7b --layers 2]
       python examples/nlp/train_llama.py --model olmoe-1b-7b --layers 1 \
           --seq-len 4096 --batch-size 2     (OLMoE's block as published:
           QK-norm, top-8 of 64 dropless experts, balance and z losses; one
           layer and its 206 M-parameter embedding and head fill a v5e)
       python examples/nlp/train_llama.py --model qwen3-next-80b-a3b \
           --layers 4 --experts-held 0:32 --vocab-rows 18992 \
           --seq-len 8192 --batch-size 1     (Qwen3-Next at one chip's share
           of a 16-way expert-parallel job: three Gated DeltaNet layers and
           one gated-attention layer, 32 of 512 experts held with the shared
           expert, an eighth of the vocabulary)
       python examples/nlp/train_llama.py --model nemotron-3-nano-30b-a3b \
           --layers 9 --experts-held 0:8 --vocab-rows 16384 \
           --seq-len 8192 --batch-size 1     (Nemotron-3-Nano at one chip's
           share of a 16-way expert-parallel job: the first nine blocks
           MEMEM*EME, four Mamba-2 mixers, four expert layers with 8 of 128
           relu2 experts held and the shared expert, one attention layer, an
           eighth of the vocabulary)
       python examples/nlp/train_llama.py --model xing4.0-29b-a4b \
           --layers 5 --experts-held 0:8 --vocab-rows 16384 \
           --seq-len 4096 --batch-size 1     (Xing4.0 at one chip's share of
           an 8-way expert-parallel job: four residual streams mixed by
           hyper-connections, the model's first five layers (two dense, three
           with 8 of 64 experts held and the shared expert), the
           multi-token-prediction depth behind them, an eighth of the
           vocabulary)
       python examples/nlp/train_llama.py --model zaya1-8b \
           --layers 5 --experts-held 0:8 --vocab-rows 32784 \
           --seq-len 8192 --batch-size 1     (ZAYA1-8B at one chip's share of
           a two-way expert-parallel job: five layers of compressed
           convolutional attention and top-1 experts behind the router that
           carries its state down the depth, 8 of 16 experts held, an eighth
           of the tied vocabulary)
       python examples/nlp/train_llama.py --model sdar-30b-a3b-chat \
           --layers 6 --experts-held 0:16 --vocab-rows 18992 \
           --seq-len 8192 --batch-size 1     (SDAR-30B-A3B-Chat's
           block-diffusion training at one chip's share of an 8-way
           expert-parallel job: every batch goes through
           hetu_tpu.dataloader.block_diffusion_noise (a level a block of 4, a
           draw a token, the mask token), the model walks the clean and the
           noised copy of a sequence in one pass under the block-diffusion
           mask and the loss is the 1/t-weighted cross-entropy on the masked
           positions; six layers, 16 of 128 experts held, an eighth of the
           vocabulary, the mask token its last row)
       python examples/nlp/train_llama.py --model phi-4-mini-flash-reasoning \
           --layers 6 --first-layer 14 --vocab-rows 25008 \
           --seq-len 16384 --batch-size 1     (Phi-4-mini-flash-reasoning at
           the pipeline stage across the boundary of its two decoders: the
           model's layers 14-19 under their published indices, Mamba-1,
           differential attention over a window of 512, the Mamba layer that
           hands out its scan output, differential attention over all keys
           that hands out its K and V, a Gated Memory Unit and a
           cross-attention layer that read them; an eighth of the tied
           vocabulary)
       python examples/nlp/train_llama.py --model evabyte --layers 4 \
           --seq-len 8192 --batch-size 1     (EvaByte at one pipeline stage of
           eight: four layers of EVA attention (exact softmax inside an
           aligned window of 2,048 bytes, one learned summary a chunk of 16
           for everything before it), eight next-byte heads over the 320
           rows, whole layers recomputed; labels are [B, S, 8], head i's the
           bytes shifted by 1 + i)
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.layers.moe import record_moe_load
from hetu_tpu.models import (LlamaConfig, LlamaForCausalLM, LLAMA_CONFIGS,
                             Qwen3NextConfig, Qwen3NextForCausalLM,
                             QWEN3_NEXT_CONFIGS, NemotronHConfig,
                             NemotronHForCausalLM, NEMOTRON_H_CONFIGS,
                             GraniteHybridConfig, GraniteHybridForCausalLM,
                             GRANITE_HYBRID_CONFIGS, OuroConfig,
                             OuroForCausalLM, OURO_CONFIGS, LagunaConfig,
                             LagunaForCausalLM, LAGUNA_CONFIGS, Xing4Config,
                             Xing4ForCausalLM, XING4_CONFIGS, Zaya1Config,
                             Zaya1ForCausalLM, ZAYA1_CONFIGS, SdarMoeConfig,
                             SdarMoeForCausalLM, SDAR_CONFIGS,
                             Phi4FlashConfig, Phi4FlashForCausalLM,
                             PHI4FLASH_CONFIGS, EvaByteConfig,
                             EvaByteForCausalLM, EVABYTE_CONFIGS,
                             record_exit_shares, load_hf_llama_weights,
                             load_hf_granite_hybrid_weights)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama-7b",
                    choices=(list(LLAMA_CONFIGS) + list(QWEN3_NEXT_CONFIGS)
                             + list(NEMOTRON_H_CONFIGS)
                             + list(GRANITE_HYBRID_CONFIGS)
                             + list(OURO_CONFIGS) + list(LAGUNA_CONFIGS)
                             + list(XING4_CONFIGS) + list(ZAYA1_CONFIGS)
                             + list(SDAR_CONFIGS)
                             + list(PHI4FLASH_CONFIGS)
                             + list(EVABYTE_CONFIGS)))
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--layers", type=int, default=0,
                    help="override layer count (0 = model default)")
    ap.add_argument("--hidden", type=int, default=0,
                    help="override hidden size (0 = model default)")
    ap.add_argument("--intermediate", type=int, default=0,
                    help="override FFN size (0 = model default)")
    ap.add_argument("--vocab", "--vocab-rows", type=int, default=0,
                    help="override vocab size (0 = model default); where "
                         "it is a slice of the published vocabulary, ids, "
                         "logits and the loss are over the slice")
    ap.add_argument("--experts-held", default=None, metavar="FIRST:COUNT",
                    help="qwen3-next, nemotron, xing4, zaya1, sdar: the experts of each "
                         "layer this chip holds, of the router's full width")
    ap.add_argument("--first-layer", type=int, default=0,
                    help="phi-4-mini-flash-reasoning: the published index of "
                         "the first of --layers consecutive layers (a "
                         "layer's kind follows from its index)")
    ap.add_argument("--heads", default=None, metavar="QUERY:KEY",
                    help="phi-4-mini-flash-reasoning, evabyte: query and key "
                         "heads (beside --hidden at a toy size)")
    ap.add_argument("--window", default=None, metavar="WINDOW:CHUNK",
                    help="evabyte: the keys of an aligned window and of a "
                         "chunk (at a toy size)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=0,
                    help="pipeline stages (graph pipeline, 1f1b)")
    ap.add_argument("--hf-import", default=None,
                    help="path to a transformers checkpoint dir to load")
    args = ap.parse_args()

    # (configs, config class, model class, the names of depth and FFN width)
    family = ((QWEN3_NEXT_CONFIGS, Qwen3NextConfig, Qwen3NextForCausalLM,
               "num_hidden_layers", "moe_intermediate_size")
              if args.model in QWEN3_NEXT_CONFIGS else
              (NEMOTRON_H_CONFIGS, NemotronHConfig, NemotronHForCausalLM,
               "num_hidden_layers", "moe_intermediate_size")
              if args.model in NEMOTRON_H_CONFIGS else
              (GRANITE_HYBRID_CONFIGS, GraniteHybridConfig,
               GraniteHybridForCausalLM, "num_hidden_layers",
               "shared_intermediate_size")
              if args.model in GRANITE_HYBRID_CONFIGS else
              (OURO_CONFIGS, OuroConfig, OuroForCausalLM,
               "num_layers", "intermediate_size")
              if args.model in OURO_CONFIGS else
              (LAGUNA_CONFIGS, LagunaConfig, LagunaForCausalLM,
               "num_hidden_layers", "moe_intermediate_size")
              if args.model in LAGUNA_CONFIGS else
              (XING4_CONFIGS, Xing4Config, Xing4ForCausalLM,
               "num_hidden_layers", "moe_intermediate_size")
              if args.model in XING4_CONFIGS else
              (ZAYA1_CONFIGS, Zaya1Config, Zaya1ForCausalLM,
               "num_hidden_layers", "moe_intermediate_size")
              if args.model in ZAYA1_CONFIGS else
              (SDAR_CONFIGS, SdarMoeConfig, SdarMoeForCausalLM,
               "num_hidden_layers", "moe_intermediate_size")
              if args.model in SDAR_CONFIGS else
              (PHI4FLASH_CONFIGS, Phi4FlashConfig, Phi4FlashForCausalLM,
               "num_hidden_layers", "intermediate_size")
              if args.model in PHI4FLASH_CONFIGS else
              (EVABYTE_CONFIGS, EvaByteConfig, EvaByteForCausalLM,
               "num_hidden_layers", "intermediate_size")
              if args.model in EVABYTE_CONFIGS else
              (LLAMA_CONFIGS, LlamaConfig, LlamaForCausalLM,
               "num_layers", "intermediate_size"))
    configs, config_cls, model_cls, depth, width = family
    base = dict(configs[args.model])
    if args.layers and args.model in NEMOTRON_H_CONFIGS:
        # a block is chosen by the pattern's character: fewer blocks are
        # the pattern's first
        from hetu_tpu.models.nemotron_h import PATTERN
        base["hybrid_override_pattern"] = PATTERN[:args.layers]
    if args.layers and args.model in GRANITE_HYBRID_CONFIGS:
        from hetu_tpu.models.granite_hybrid import LAYER_TYPES
        base["layer_types"] = LAYER_TYPES[:args.layers]
    for field, val in ((depth, args.layers), ("hidden_size", args.hidden),
                       (width, args.intermediate),
                       ("vocab_size", args.vocab)):
        if val:
            base[field] = val
    if args.experts_held:
        base["experts_held"] = tuple(
            int(n) for n in args.experts_held.split(":"))
    if args.model in PHI4FLASH_CONFIGS:
        # a cut keeps the published indices: the model's own depth says
        # where its two decoders meet
        base.update(first_layer_index=args.first_layer, remat="layer",
                    published_layers=Phi4FlashConfig().num_layers)
    if args.model in EVABYTE_CONFIGS:
        base["remat"] = "layer"
        if args.window:
            base["window_size"], base["chunk_size"] = (
                int(n) for n in args.window.split(":"))
    if args.heads and args.model in (*PHI4FLASH_CONFIGS, *EVABYTE_CONFIGS):
        base["num_attention_heads"], base["num_key_value_heads"] = (
            int(n) for n in args.heads.split(":"))
    diffusion = args.model in SDAR_CONFIGS
    if diffusion and args.vocab:
        # the mask token is an ordinary row of the slice: its last
        base["mask_token_id"] = args.vocab - 1
    c = config_cls(seq_len=args.seq_len, **base)
    rng = np.random.default_rng(0)
    B, S = args.batch_size, args.seq_len

    # block diffusion walks a clean and a noised copy of a sequence in one
    # pass and weighs each masked position's loss
    ids = ht.placeholder_op("ids", (B, 2 * S if diffusion else S),
                            dtype=np.int32)
    # a byte-level model with several heads labels each position once a head
    P = getattr(c, "num_pred_heads", 0)
    labels = ht.placeholder_op("labels", (B, S, P) if P else (B, S),
                               dtype=np.int32)
    weights = (ht.placeholder_op("weights", (B, S), dtype=np.float32)
               if diffusion else None)
    model = model_cls(c, pipeline_stages=args.pp or None)
    loss = (model.loss(ids, labels, weights) if diffusion
            else model.loss(ids, labels))
    opt = ht.AdamWOptimizer(learning_rate=args.lr, weight_decay=0.01)

    kwargs = dict(compute_dtype=jnp.bfloat16)
    if args.pp:
        from hetu_tpu.parallel import make_mesh
        kwargs.update(mesh=make_mesh({"pp": args.pp}), pipeline="1f1b",
                      num_micro=max(2, args.pp))
    elif args.tp > 1 or args.dp > 1:
        from hetu_tpu.parallel import MegatronLM
        kwargs.update(dist_strategy=MegatronLM(dp=args.dp, tp=args.tp))
    # an MoE model's per-expert load rides the loss's fetch: [2, E] a layer
    # (and a sigmoid-scored router's selection bias, [E] a layer)
    loads = model.moe_loads() if c.num_experts and not args.pp else []
    biases = (model.router_biases()
              if loads and hasattr(model, "router_biases") else [])
    # a looped model's mean exit shares, [P], ride it too
    shares = [model.exit_shares] if args.model in OURO_CONFIGS else []
    ex = ht.Executor({"train": ([loss, opt.minimize(loss)] + loads + biases
                                + shares)}, **kwargs)

    if args.hf_import:
        import transformers
        hf = transformers.AutoModelForCausalLM.from_pretrained(
            args.hf_import)
        (load_hf_granite_hybrid_weights
         if args.model in GRANITE_HYBRID_CONFIGS
         else load_hf_llama_weights)(ex, model, hf.state_dict())
        print(f"imported weights from {args.hf_import}")

    for step in range(args.steps):
        if diffusion:
            tok = rng.integers(0, c.vocab_size - 1, (B, S))
            tok = tok + (tok >= c.mask_token_id)    # never the mask token
            feed = dict(zip((ids, labels, weights), ht.block_diffusion_noise(
                tok, c.block_length, c.mask_token_id, rng)))
        elif P:
            tok = rng.integers(0, c.vocab_size, (B, S + P))
            feed = {ids: tok[:, :S], labels: np.stack(
                [tok[:, 1 + i:1 + i + S] for i in range(P)], axis=-1)}
        else:
            tok = rng.integers(0, c.vocab_size, (B, S + 1))
            feed = {ids: tok[:, :-1], labels: tok[:, 1:]}
        out = ex.run("train", feed_dict=feed,
                     convert_to_numpy_ret_vals=True)
        for i, load in enumerate(out[2:2 + len(loads)]):
            record_moe_load(f"layer{i}", load,
                            bias=out[2 + len(loads) + i] if biases else None)
        if shares:
            record_exit_shares(out[-1])
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {out[0]:.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
