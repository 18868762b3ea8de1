"""The controls behind the limits of ``traffic/b1-s8192-ouro.json``, each
through the harness's own comparison.

    python3 -m chipbench.reference.ouro_controls --seed <n> [--control <name> ...]

Builds the cell ``ouro-2.6b.b1-s8192``'s program from ``--seed`` (its f32
masters are the weights, the seed's first batch the data) and runs the plain
reference (``reference/ouro.py``) in f32 at the highest precision, as the
cell's run does before its first step: the baseline.  Then the reference
again under each control of ``CONTROLS`` (both operands of every matrix
product rounded to a lower precision, or a piece of the mathematics left
out), and it hands the control's terms to ``loops.TrainLoop.finish`` IN THE
PROGRAM'S PLACE: the traffic file's ``reference_tolerance`` terms and the
first training loss (a control's first loss is its own loss) are compared by
the code that decides a run's ``correct``, with the limits of the traffic
file as it stands.  Last comes the program itself (``eval_loss``).

One JSON line a control: ``{"control", "correct", "refused_by", "gaps"}``
(``gaps``: the distance of each term from the baseline, beside which the
limits were set).  Every control but ``bf16`` has to come out ``correct:
false`` and the program ``correct: true``, else the exit code is 1: a limit
that refuses nothing, or one the program does not pass.  ``bf16`` is the
program's own precision: reported, held to nothing.  It needs the chip the
cell needs; ``--rehearsal`` runs the toy on the CPU, in f32, where the limits
are the toy's (``tests/test_ouro_cell.py``).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from .. import loops, run
from ..builders import ouro as builder
from ..builders.granite_hybrid import logits_gap

CELL = "ouro-2.6b.b1-s8192"
#: name -> how ``builders/ouro.py reference_run`` departs from the baseline
CONTROLS = {
    "bf16": dict(matmul_inputs="bfloat16"),
    "fp8_e4m3": dict(matmul_inputs="float8_e4m3fn"),
    "fp8_e5m2": dict(matmul_inputs="float8_e5m2"),
    "a_pass": dict(passes=-1),                  # P - 1 passes for P
    "post_norms": dict(leave_out=("post_norms",)),
    "fed_norm": dict(leave_out=("fed_norm",)),
    "last_takes_rest": dict(leave_out=("last_takes_rest",)),
    "entropy": dict(leave_out=("entropy",)),
}
HELD_TO_NOTHING = ("bf16",)


class InPlace:
    """What ``TrainLoop.finish`` asks of a program beside its losses, for a
    control that stands in the program's place: nothing ran, so nothing was
    retraced and no kernel chosen."""
    tokens_per_step = 0

    def uniform_loss(self):
        return float("nan")

    def retraces(self):
        return 0

    def kernel_choices(self):
        return [], []

    def pallas_ops(self):
        return ()


def verdict(mix, want, got):
    """``(correct, refused_by)`` of a program whose validate reading is
    ``got`` and whose first training loss is ``got["loss"]``, against the
    reference's ``want``: ``TrainLoop.finish``'s own checks of the traffic
    file's terms and of the first loss, nothing copied from it."""
    loop = loops.TrainLoop(InPlace(), mix, 0, loops.Spans(),
                           lambda msg: None)
    loop.ref_loss, loop.eval_loss = want, got
    loop.warm_losses, loop.retraces0 = [got["loss"]], 0
    loop.rec = {"t0": 0.0, "step_ends": [1.0], "losses": [got["loss"]]}
    terms = list(mix["reference_tolerance"]) + ["first_loss"]
    checks = loop.finish()[:len(terms)]
    assert all(t.split("_")[0] in what for t, (_, what) in zip(terms, checks))
    refused = [t for t, (ok, _) in zip(terms, checks) if not ok]
    return not refused, refused


def control_terms(base, p0, logits0, got, p, logits):
    """A control's terms as ``eval_loss`` names them: its own loss and
    terms, and its gaps from the baseline's distribution and logits (a
    control with fewer passes has no mass in the passes it lacks)."""
    whole = np.zeros_like(p0)
    whole[:len(p)] = p
    return dict(got, exit_gap=float(np.abs(whole - p0).max()),
                logits_gap=max(logits_gap(a, b)
                               for a, b in zip(logits, logits0)))


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python3 -m chipbench.reference.ouro_controls")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", action="append", choices=list(CONTROLS))
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
    prog = builder.build(config, mix, ns.seed, say)
    feed = prog.make_batches(ns.seed, 1)[0]
    ids, labels = feed[prog.nodes["ids"]], feed[prog.nodes["labels"]]
    beta = config["job"]["exit_entropy_coeff"]
    params = builder.reference_params(prog.model, prog.ex.params)
    base = prog.reference_loss(feed, int(mix["reference_chunk"]))
    p0, logits0 = prog._ref_p, list(prog._ref_logits)
    say(f"the baseline, f32 at the highest precision: {base}")
    wrong = []
    for name in ns.control or CONTROLS:
        how = dict(CONTROLS[name])
        if "matmul_inputs" in how:
            how["matmul_inputs"] = getattr(jnp, how["matmul_inputs"])
        if "passes" in how:
            how["passes"] += config["total_ut_steps"]
        got, p, logits, _ = builder.reference_run(params, config, ids,
                                                  labels, beta, **how)
        got = control_terms(base, p0, logits0, got, p, logits)
        del logits
        correct, refused = verdict(mix, base, got)
        print(json.dumps({"control": name, "seed": ns.seed,
                          "correct": correct, "refused_by": refused,
                          "gaps": {k: abs(v - base[k])
                                   for k, v in got.items()}}), flush=True)
        if correct and name not in HELD_TO_NOTHING:
            wrong.append(f"{name} passes every limit")
    mine = prog.eval_loss(feed)
    correct, refused = verdict(mix, base, mine)
    print(json.dumps({"control": "program", "seed": ns.seed,
                      "correct": correct, "refused_by": refused,
                      "gaps": {k: abs(v - base[k])
                               for k, v in mine.items()}}), flush=True)
    if not correct:
        wrong.append(f"the program is refused by {refused}")
    prog.close()
    for what in wrong:
        say(f"WRONG {what}")
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
