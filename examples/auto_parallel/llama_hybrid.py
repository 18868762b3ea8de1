"""Searched hybrid-parallel Llama training (reference:
tools/Hetu-Galvatron/galvatron/models/llama/train_dist.py — search a
per-layer (tp, dp-type, ckpt) x pipeline config, then train under it).

Profiles a Llama layer stack, runs the Galvatron search, builds the
LlamaHPLayer model under the searched config (RoPE/GQA/SwiGLU per-layer
TP x DP/FSDP, searched pipeline schedule), and runs a few training steps.

Usage (8 virtual devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/auto_parallel/llama_hybrid.py --preset tiny
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import jax

import jax.numpy as jnp

from hetu_tpu.galvatron import (GalvatronSearch, LayerProfile, LlamaHPLayer,
                                make_lm_hybrid_model)

PRESETS = {
    # hidden, layers, heads, kv_heads, ffn  (tiny = CI-sized)
    "tiny": (32, 4, 4, 2, 64),
    "llama-7b-ish": (4096, 32, 32, 32, 11008),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--world", type=int, default=None)
    ap.add_argument("--mem-gb", type=float, default=16.0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--embed-sdp", dest="embed_sdp", type=int, default=0,
                    help="FSDP-shard the embedding/head rows (reference "
                         "embed_sdp flag)")
    args = ap.parse_args()

    h, n_layers, heads, kv_heads, ffn = PRESETS[args.preset]
    world = args.world or len(jax.devices())

    # 1. profile (analytic; swap in profiler.py measurements for real runs)
    per_layer_params = 4 * h * h + 3 * h * ffn
    act_bytes = 10 * args.seq_len * h * 2
    layers = [LayerProfile(2.0, per_layer_params * 4, act_bytes)
              for _ in range(n_layers)]

    # 2. search
    from hetu_tpu.galvatron import measure_ici_gbps
    ici = measure_ici_gbps() or 100.0        # measured hardware bandwidth
    cfg = GalvatronSearch(world, args.mem_gb * (1 << 30),
                          micro_bsz=2, ici_gbps=ici).search(layers)
    print(f"searched config (ici {ici:.1f} GB/s):", cfg.to_json())

    # 3. build + train the FULL LM under the searched config: vocab-parallel
    #    embedding + RMS-normed head wrap onto the first/last stage
    #    (embed_sdp), tokens in → CE loss out (reference train_dist.py)
    specs = [LlamaHPLayer(hidden=h, heads=heads, kv_heads=kv_heads, ffn=ffn)
             for _ in range(n_layers)]
    model = make_lm_hybrid_model(args.vocab, specs, cfg,
                                 embed_sdp=args.embed_sdp, norm="rms")
    params = model.init_params(jax.random.PRNGKey(0))
    step, opt_init = model.make_train_step(lr=1e-2)
    opt_state = opt_init(params)

    kx, kt = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.randint(kx, (args.batch, args.seq_len), 0, args.vocab)
    tgt = jax.random.randint(kt, (args.batch, args.seq_len), 0, args.vocab)
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, x, tgt)
        print(f"step {i} loss {float(loss):.5f} "
              f"(schedule={cfg.pipeline_type}, pp={cfg.pp_deg}, "
              f"tp={cfg.tp_sizes[0]}, sp={cfg.sp_flags[0]}, "
              f"embed_sdp={args.embed_sdp})")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
