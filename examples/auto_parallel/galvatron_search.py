"""Per-layer hybrid-parallel strategy search (reference:
tools/Hetu-Galvatron — profile, search, emit the layer config).

Profiles a transformer-ish layer stack analytically, runs the native DP
core over (tp size, DDP-vs-FSDP, activation ckpt) per layer x pipeline
degree, and prints the chosen per-layer strategy JSON.
Usage: python examples/auto_parallel/galvatron_search.py --world 8
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse
import json

import jax

from hetu_tpu.galvatron import (LayerProfile, GalvatronSearch)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--world", type=int, default=8)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=2560)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--mem-gb", type=float, default=16.0)
    ap.add_argument("--micro-bsz", type=int, default=2)
    ap.add_argument("--out", default=None, help="write config JSON here")
    ap.add_argument("--measure", action="store_true",
                    help="profile real HP layers (time + XLA memory "
                         "ledger) and the mesh's psum bandwidth instead "
                         "of analytic estimates")
    args = ap.parse_args()

    h, s = args.hidden, args.seq_len
    if args.measure:
        from hetu_tpu.galvatron import (TransformerHPLayer,
                                        measure_ici_gbps,
                                        profile_hp_layers)
        specs = [TransformerHPLayer(hidden=h, heads=max(1, h // 64))
                 for _ in range(args.layers)]
        # profile at the REAL sequence length: compute and memory terms
        # scale super-linearly with seq, so capping here would feed the
        # search numbers from a different workload than the emitted config
        layers = profile_hp_layers(specs, batch=2, seq=s)
        ici = measure_ici_gbps() or 100.0
    else:
        per_layer_params = 12 * h * h
        act_bytes = 10 * s * h * 2      # bf16 activations per sample
        compute_ms = 2.0                 # per-layer fwd estimate
        layers = [LayerProfile(compute_ms, per_layer_params * 4, act_bytes)
                  for _ in range(args.layers)]
        ici = 100.0

    search = GalvatronSearch(args.world, args.mem_gb * (1 << 30),
                             micro_bsz=args.micro_bsz, ici_gbps=ici)
    cfg = search.search(layers)
    out = cfg.to_json()
    print(json.dumps(out, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
