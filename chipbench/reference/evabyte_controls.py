"""The controls behind the limits of ``traffic/b1-s8192-evabyte.json``, each
through the harness's own comparison.

    python3 -m chipbench.reference.evabyte_controls --seed <n> [--control <name> ...]

Builds the cell ``evabyte-6.5b.b1-s8192``'s program from ``--seed`` (its f32
masters are the weights, the seed's first batch the data) and runs the plain
reference (``reference/evabyte.py``) in f32 at the highest precision, as the
cell's run does before its first step: the baseline.  Then the reference again
under each control (both operands of every matrix product rounded to a lower
precision, or one piece of ``reference/evabyte.py CONTROLS`` changed), and it
hands the control's terms to ``loops.TrainLoop.finish`` IN THE PROGRAM'S PLACE
(``ouro_controls.verdict``): the traffic file's ``reference_tolerance`` terms
and the first training loss (a control's first loss is its own loss) are
compared by the code that decides a run's ``correct``, with the limits of the
traffic file as it stands.  Last comes the program itself (``eval_loss``).

One JSON line a control: ``{"control", "correct", "refused_by", "gaps"}``
(``gaps``: the distance of each term from the baseline, beside which the
limits were set).  Every control but ``bf16`` and ``summaries_bf16`` has to
come out ``correct: false`` and the program ``correct: true``, else the exit
code is 1.  ``bf16`` is the program's own precision: reported, held to
nothing.  So is ``summaries_bf16`` ON THE CHIP: the program's keys and values
are bf16 before they are summarised, which alone moves its summaries 0.6%
from the f32 reference's, and sums rounded to bf16 on f32 keys move them 0.2%
(my chip run, PR 67, call 67.1): no limit can lie between; the toy's f32
program is held to it (``tests/test_evabyte_cell.py``: it fails
``summary_gap`` there).  It needs the chip the cell needs; ``--rehearsal``
runs the toy on the CPU, in f32, where the limits are the toy's.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from .. import run
from ..builders import evabyte as builder
from ..builders.granite_hybrid import logits_gap
from . import evabyte as ref
from .ouro_controls import verdict

CELL = "evabyte-6.5b.b1-s8192"
PRECISIONS = {"bf16": "bfloat16", "fp8_e4m3": "float8_e4m3fn",
              "fp8_e5m2": "float8_e5m2"}
HELD_TO_NOTHING = ("bf16", "summaries_bf16")


def control_terms(prog, base, kept, got):
    """A control's terms as ``eval_loss`` names them: its own loss and heads,
    its gaps from what the baseline kept.  The control's own are what
    ``reference_loss`` left on ``prog.kept``."""
    mine = prog.kept
    return dict(base, **{k: v for k, v in got.items()
                         if k.startswith(("loss", "ce"))},
                logits_gap=logits_gap(mine["logits"], kept["logits"]),
                eva_gap=logits_gap(mine["eva"], kept["eva"]),
                eva_remote_gap=builder.remote_gap(
                    mine["eva"], kept["eva"], kept["local"],
                    prog.config["window_size"]),
                summary_gap=logits_gap(mine["summaries"], kept["summaries"]))


def main(argv=None):
    names = list(PRECISIONS) + list(ref.CONTROLS)
    ap = argparse.ArgumentParser(
        prog="python3 -m chipbench.reference.evabyte_controls")
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
