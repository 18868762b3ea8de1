"""The controls behind the limits of ``traffic/b1-s16384-phi4flash.json``, each
through the harness's own comparison.

    python3 -m chipbench.reference.phi4flash_controls --seed <n> [--control <name> ...]

Builds the cell ``phi-4-mini-flash.b1-s16384``'s program from ``--seed`` (its
f32 masters are the weights, the seed's first batch the data) and runs the
plain reference (``reference/phi4flash.py``) in f32 at the highest precision,
as the cell's run does before its first step: the baseline.  Then the
reference again under each control (both operands of every matrix product
rounded to a lower precision, or one piece of ``reference/phi4flash.py
CONTROLS`` changed: a bf16 state, ``A^2`` not subtracted, ``lambda_init`` at the
cut's index, no sub-norm, ``M`` taken after the gate, the cross layer on its
own K and V, a window of 511 and of 513, the memory's skip left out), and it
hands the control's terms to ``loops.TrainLoop.finish`` IN THE PROGRAM'S PLACE
(``ouro_controls.verdict``): the traffic file's ``reference_tolerance`` terms
and the first training loss (a control's first loss is its own loss) are
compared by the code that decides a run's ``correct``, with the limits of the
traffic file as it stands.  Last comes the program itself (``eval_loss``).

One JSON line a control: ``{"control", "correct", "refused_by", "gaps"}``
(``gaps``: the distance of each term from the baseline, beside which the
limits were set).  Every control but ``bf16`` has to come out ``correct:
false`` and the program ``correct: true``, else the exit code is 1: a limit
that refuses nothing, or one the program does not pass.  ``bf16`` is the
program's own precision: reported, held to nothing.  It needs the chip the
cell needs; ``--rehearsal`` runs the toy on the CPU, in f32, where the limits
are the toy's (``tests/test_phi4flash_cell.py``).
"""

from __future__ import annotations

import argparse
import json
import sys

from .. import run
from ..builders import phi4flash as builder
from ..builders.granite_hybrid import logits_gap
from ..builders.laguna import edge_share
from . import phi4flash as ref
from .ouro_controls import verdict

CELL = "phi-4-mini-flash.b1-s16384"
PRECISIONS = {"bf16": "bfloat16", "fp8_e4m3": "float8_e4m3fn",
              "fp8_e5m2": "float8_e5m2"}
HELD_TO_NOTHING = ("bf16",)


def control_terms(prog, base, kept, got, name):
    """A control's terms as ``eval_loss`` names them: its own loss, its gaps
    from what the baseline kept, the window its window layer lies nearest
    among the BASELINE's three, and (the state's type alone) the probe of
    the recurrence carried in that type."""
    import jax.numpy as jnp
    mine = prog.kept
    out = dict(base, loss=got["loss"], ce=got["ce"],
               logits_gap=logits_gap(mine["logits"], kept["logits"]),
               window_edge=edge_share(mine["window"], kept["window"],
                                      kept["edges"]))
    for probe, gap in builder.GAPS.items():
        out[gap] = logits_gap(mine[probe], kept[probe])
    if name == "bf16_state":
        out["scan_probe_gap"] = prog.scan_probe_gap(
            lambda u, *rest: ref.recurrence(u.astype(jnp.float32), *rest,
                                            state_dtype=jnp.bfloat16))
    return out


def main(argv=None):
    names = list(PRECISIONS) + list(ref.CONTROLS)
    ap = argparse.ArgumentParser(
        prog="python3 -m chipbench.reference.phi4flash_controls")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", action="append", choices=names)
    ap.add_argument("--rehearsal", action="store_true")
    ns = ap.parse_args(argv)

    def say(msg):
        print(f"chipbench: {msg}", flush=True)

    import jax
    import jax.numpy as jnp
    _, _, config, mix = run.load_cell(CELL)
    if ns.rehearsal:
        config = run.merge(config, config["toy"])
        mix = run.merge(mix, mix["toy"])
    wanted = "cpu" if ns.rehearsal else "tpu"
    if jax.devices()[0].platform != wanted:
        say(f"FAIL: needs platform {wanted!r}, jax found "
            f"{jax.devices()[0].platform!r}. Nothing was run.")
        return 3
    from hetu_tpu import telemetry
    telemetry.enable()
    prog = builder.build(config, mix, ns.seed, say)
    feed = prog.make_batches(ns.seed, 1)[0]
    chunk = int(mix["reference_chunk"])
    base = prog.reference_loss(feed, chunk)
    kept = prog.kept
    say(f"the baseline, f32 at the highest precision: {base}")
    wrong = []

    def report(name, got):
        correct, refused = verdict(mix, base, got)
        print(json.dumps({"control": name, "seed": ns.seed,
                          "correct": correct, "refused_by": refused,
                          "gaps": {k: abs(v - base[k])
                                   for k, v in got.items()}}), flush=True)
        return correct, refused
    for name in ns.control or names:
        how = ({"matmul_inputs": getattr(jnp, PRECISIONS[name])}
               if name in PRECISIONS else {"without": (name,)})
        correct, _ = report(name, control_terms(
            prog, base, kept, prog.reference_loss(feed, chunk, **how), name))
        if correct and name not in HELD_TO_NOTHING:
            wrong.append(f"{name} passes every limit")
    prog.kept = dict(kept)
    correct, refused = report("program", prog.eval_loss(feed))
    if not correct:
        wrong.append(f"the program is refused by {refused}")
    prog.close()
    telemetry.shutdown()
    for what in wrong:
        say(f"WRONG {what}")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
