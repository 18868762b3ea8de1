"""The controls behind the limits of ``traffic/ep4-b4-s8192-mellum.json``, each
through the harness's own comparison.

    python3 -m chipbench.reference.mellum_controls --seed <n> [--control <name> ...]

Builds the cell ``mellum2-12b-a2.5b.ep4-b4-s8192``'s program from ``--seed``
under its strategy on the four chips (its f32 masters, gathered from the chips
that hold them, are the weights, the seed's first batch the data) and runs the plain
reference (``reference/mellum.py``) in f32 at the highest precision, as the
cell's run does before its first step: the baseline.  Then the reference
again under each control (both operands of every matrix product rounded to a
lower precision, or one piece of ``reference/mellum.py CONTROLS`` changed:
three of them what an exchange of experts gets wrong when it is wrong),
and it hands the control's terms to ``loops.TrainLoop.finish`` IN THE
PROGRAM'S PLACE (``ouro_controls.verdict``): the traffic file's
``reference_tolerance`` terms and the first training loss (a control's first
loss is its own loss) are compared by the code that decides a run's
``correct``, with the limits of the traffic file as it stands.  Last comes
the program itself (``eval_loss``).

One JSON line a control: ``{"control", "correct", "refused_by", "gaps"}``
(``gaps``: the distance of each term from the baseline, beside which the
limits were set).  Every control but ``bf16`` has to come out ``correct:
false`` and the program ``correct: true``, else the exit code is 1: a limit
that refuses nothing, or one the program does not pass.  ``bf16`` is the
program's own precision: reported, held to nothing.  It needs the chip the
cell needs; ``--rehearsal`` runs the toy on the CPU, in f32, where the limits
are the toy's (``tests/test_mellum_cell.py``).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from .. import run
from ..builders import mellum as builder
from ..builders.laguna import edge_share
from ..builders.granite_hybrid import logits_gap
from . import mellum as ref
from .ouro_controls import verdict

CELL = "mellum2-12b-a2.5b.ep4-b4-s8192"
PRECISIONS = {"bf16": "bfloat16", "fp8_e4m3": "float8_e4m3fn",
              "fp8_e5m2": "float8_e5m2"}
HELD_TO_NOTHING = ("bf16",)


def control_terms(prog, base, kept, got):
    """A control's terms as ``eval_loss`` names them: its own loss, its gaps
    from what the baseline kept (``kept``: logits, attention outputs, the
    windows one key off, the routed sum, the experts chosen), and the share
    of the baseline's pairs of the first expert layer it chose too.  The
    control's own are what ``reference_loss`` left on ``prog.kept``."""
    mine = prog.kept
    chose, theirs = mine["chosen"][0], kept["chosen"][0]
    shared = sum(len(np.intersect1d(a, b)) for a, b in zip(chose, theirs))
    return dict(base, loss=got["loss"], ce=got["ce"],
                logits_gap=logits_gap(mine["logits"], kept["logits"]),
                window_gap=logits_gap(mine["window"], kept["window"]),
                window_edge=edge_share(mine["window"], kept["window"],
                                               kept["edges"]),
                full_gap=logits_gap(mine["full"], kept["full"]),
                routed_gap=logits_gap(mine["routed"], kept["routed"]),
                routing_share=shared / theirs.size)


def main(argv=None):
    names = list(PRECISIONS) + list(ref.CONTROLS)
    ap = argparse.ArgumentParser(
        prog="python3 -m chipbench.reference.mellum_controls")
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
    telemetry.enable()      # the registry counts the attention nodes built
    prog = builder.build(config, mix, ns.seed, say)
    feed = prog.make_batches(ns.seed, 1)[0]
    chunk = int(mix["reference_chunk"])
    base = prog.reference_loss(feed, chunk)
    kept = prog.kept
    say(f"the baseline, f32 at the highest precision: {base}")
    wrong = []
    for name in ns.control or names:
        how = ({"matmul_inputs": getattr(jnp, PRECISIONS[name])}
               if name in PRECISIONS else {"without": (name,)})
        got = control_terms(prog, base, kept,
                            prog.reference_loss(feed, chunk, **how))
        correct, refused = verdict(mix, base, got)
        print(json.dumps({"control": name, "seed": ns.seed,
                          "correct": correct, "refused_by": refused,
                          "gaps": {k: abs(v - base[k])
                                   for k, v in got.items()}}), flush=True)
        if correct and name not in HELD_TO_NOTHING:
            wrong.append(f"{name} passes every limit")
    prog.kept = kept
    mine = prog.eval_loss(feed)
    correct, refused = verdict(mix, base, mine)
    print(json.dumps({"control": "program", "seed": ns.seed,
                      "correct": correct, "refused_by": refused,
                      "gaps": {k: abs(v - base[k])
                               for k, v in mine.items()}}), flush=True)
    if not correct:
        wrong.append(f"the program is refused by {refused}")
    prog.close()
    telemetry.shutdown()
    for what in wrong:
        say(f"WRONG {what}")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
