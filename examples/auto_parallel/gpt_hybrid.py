"""Searched hybrid-parallel GPT training (reference:
tools/Hetu-Galvatron/galvatron/models/gpt/train_dist.py — search a
per-layer (tp, dp-type, ckpt, sp) x pipeline config, then train the full
LM under it).

Profiles a GPT layer stack, runs the Galvatron search, wraps the searched
config with a vocab-parallel embedding + tied-or-untied LM head
(embed_sdp honored), and runs a few training steps on token data.

Usage (8 virtual devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/auto_parallel/gpt_hybrid.py --preset tiny
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import jax

from hetu_tpu.galvatron import (GalvatronSearch, LayerProfile,
                                TransformerHPLayer, make_lm_hybrid_model)

PRESETS = {
    # hidden, layers, heads
    "tiny": (32, 4, 4),
    "gpt2-small-ish": (768, 12, 12),
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
    ap.add_argument("--embed-sdp", dest="embed_sdp", type=int, default=0)
    ap.add_argument("--untied", action="store_true",
                    help="separate LM-head weights (default: GPT-2 tying "
                         "when the searched config keeps pp_deg == 1)")
    args = ap.parse_args()

    h, n_layers, heads = PRESETS[args.preset]
    world = args.world or len(jax.devices())

    per_layer_params = 12 * h * h
    act_bytes = 10 * args.seq_len * h * 2
    layers = [LayerProfile(2.0, per_layer_params * 4, act_bytes)
              for _ in range(n_layers)]

    from hetu_tpu.galvatron import measure_ici_gbps
    ici = measure_ici_gbps() or 100.0        # measured hardware bandwidth
    cfg = GalvatronSearch(world, args.mem_gb * (1 << 30),
                          micro_bsz=2, ici_gbps=ici).search(layers)
    print(f"searched config (ici {ici:.1f} GB/s):", cfg.to_json())

    specs = [TransformerHPLayer(hidden=h, heads=heads)
             for _ in range(n_layers)]
    tie = (not args.untied) and cfg.pp_deg == 1
    model = make_lm_hybrid_model(args.vocab, specs, cfg,
                                 embed_sdp=args.embed_sdp,
                                 tie_embeddings=tie)
    params = model.init_params(jax.random.PRNGKey(0))
    step, opt_init = model.make_train_step(lr=1e-2)
    opt_state = opt_init(params)

    kx, kt = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.randint(kx, (args.batch, args.seq_len), 0, args.vocab)
    tgt = jax.random.randint(kt, (args.batch, args.seq_len), 0, args.vocab)
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, x, tgt)
        print(f"step {i} loss {float(loss):.5f} "
              f"(pp={cfg.pp_deg}, tp={cfg.tp_sizes[0]}, "
              f"sp={cfg.sp_flags[0]}, tied={tie})")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
