"""The loops that offer a traffic mix to a program, one per traffic kind
(today one: ``train_loop``).

A loop has three phases: ``prepare`` (set-up: build the ring of batches from
the seed, take the correctness sample, warm every shape the window will
use), ``window`` (the measured ``--seconds``; nothing is built, compiled or
collected here) and ``finish`` (the checks that need a quiet system).  What
a loop saw goes into a plain record, from which the readers under
``metrics/`` take every metric.

Host spans are the benchmark's own: every call into the program is wrapped
in a ``jax.profiler.TraceAnnotation`` (so it is in the profiler's trace, on
the device trace's clock) and stamped on ``time.perf_counter``.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

now = time.perf_counter

#: host span names, innermost first: the order in which an idle gap of the
#: device is attributed
SPAN_ORDER = ("feed", "executor_run")
STEP_SPANS = ("executor_run",)


class Spans:
    """Host spans on the host clock and, when a trace is on, in it."""

    def __init__(self):
        self.rec = {}

    @contextlib.contextmanager
    def span(self, name):
        import jax
        with jax.profiler.TraceAnnotation(name):
            t0 = now()
            try:
                yield
            finally:
                self.rec.setdefault(name, []).append((t0, now()))


class Tracer:
    """Switches the profiler on for the last ``trace_seconds`` of a window;
    made without a directory it never does.  Python call tracing is off: it
    slows the host and is not read."""

    def __init__(self, out_dir=None, window_seconds=0.0, trace_seconds=0.0):
        self.dir = out_dir
        self.start_at = max(0.0, window_seconds - trace_seconds)
        self.started_at = self.stopped_at = None
        self.start_cost = self.stop_cost = 0.0

    def tick(self, elapsed):
        if (self.dir is not None and self.started_at is None
                and elapsed >= self.start_at):
            import jax
            t = now()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.started_at = now()
            self.start_cost = self.started_at - t

    def stop(self):
        if self.started_at is not None and self.stopped_at is None:
            import jax
            t = now()
            jax.profiler.stop_trace()
            self.stopped_at = now()
            self.stop_cost = self.stopped_at - t


def fmt(terms):
    return ", ".join(f"{k} {v:.6f}" for k, v in terms.items())


def quantile(values, q):
    """The ``q`` quantile by linear interpolation, as numpy's default."""
    return float(np.quantile(np.asarray(values, float), q))


# -- training -----------------------------------------------------------------

class TrainLoop:
    """``ex.run("train", feed_dict=<a fresh numpy batch>,
    convert_to_numpy_ret_vals=True)`` every step: the loop of
    ``examples/nlp/train_bert.py``, with the batches drawn beforehand."""

    def __init__(self, program, mix, seed, spans, say):
        self.p, self.mix, self.seed = program, mix, seed
        self.spans, self.say = spans, say
        self.checks = []

    def prepare(self):
        p, mix = self.p, self.mix
        self.batches = p.make_batches(self.seed, int(mix["ring"]))
        t = now()
        self.ref_loss = p.reference_loss(self.batches[0],
                                         int(mix["reference_chunk"]))
        t_ref = now() - t
        t = now()
        self.eval_loss = p.eval_loss(self.batches[0])
        self.say(f"before the first step, on the first batch with dropout "
                 f"off: program's {fmt(self.eval_loss)} ({now() - t:.1f} "
                 f"s), plain reference's {fmt(self.ref_loss)} "
                 f"({t_ref:.1f} s)")
        self.warm_losses = []
        t = now()
        for i in range(int(mix["warm_steps"])):
            self.warm_losses.append(p.step(self.batches[i % len(
                self.batches)]))
            if i == 0:
                self.say(f"first step (traces, compiles or loads) "
                         f"{now() - t:.1f} s")
        self.retraces0 = p.retraces()

    def window(self, seconds, tracer):
        p, spans, batches = self.p, self.spans, self.batches
        n = len(batches)
        ends, losses = [], []
        t0 = now()
        while True:
            elapsed = now() - t0
            if elapsed >= seconds:
                break
            tracer.tick(elapsed)
            with spans.span("feed"):
                feed = batches[len(ends) % n]
            with spans.span("executor_run"):
                losses.append(p.step(feed))
            ends.append(now())
        tracer.stop()
        self.rec = {"kind": "train", "t0": t0, "step_ends": ends,
                    "losses": losses,
                    "tokens_per_step": p.tokens_per_step,
                    "trace_started_at": tracer.started_at}
        return self.rec

    def finish(self):
        p, rec = self.p, self.rec
        losses = self.warm_losses + rec["losses"]
        check = self.checks.append
        for term, tol in self.mix["reference_tolerance"].items():
            got, want = self.eval_loss[term], self.ref_loss[term]
            check((abs(got - want) < tol,
                   f"the program's {term} with dropout off {got:.6f} "
                   f"within {tol} of the plain reference's {want:.6f} on "
                   f"the same batch and weights (gap "
                   f"{abs(got - want):.6f})"))
        tol = float(self.mix["first_loss_tolerance"])
        want = self.ref_loss["loss"]
        check((abs(losses[0] - want) < tol,
               f"the first training loss (dropout on) {losses[0]:.4f} "
               f"within {tol} of the plain reference's {want:.4f} on the "
               f"same batch and weights (gap {abs(losses[0] - want):.4f})"))
        uniform = p.uniform_loss()
        self.say(f"first loss {losses[0]:.3f} against the uniform guess "
                 f"{uniform:.3f}: {losses[0] - uniform:+.3f} (not a check: "
                 f"see first_loss_tolerance_why in the traffic file)")
        check((bool(np.all(np.isfinite(losses))), "every loss is finite"))
        check((p.retraces() == self.retraces0,
               "no retrace or compilation inside the window"))
        taken, fallbacks = p.kernel_choices()
        check((not fallbacks and set(p.pallas_ops()) <= set(taken),
               f"while the step was traced the Pallas form was chosen for "
               f"{taken} (wanted {list(p.pallas_ops())}) and no jnp form "
               f"the model does not explain was ({fallbacks})"))
        rec["attempted"] = len(rec["step_ends"])
        rec["failed"] = int(np.sum(~np.isfinite(rec["losses"])))
        steps = np.diff([rec["t0"]] + rec["step_ends"]) * 1e3
        p50 = quantile(steps, 0.5)
        over = np.maximum(steps - p50, 0.0)
        self.say(f"steps {len(steps)}: step time p50 {p50:.2f} ms, p90 "
                 f"{quantile(steps, 0.9):.2f} ms, p99 "
                 f"{quantile(steps, 0.99):.2f} ms, max {steps.max():.2f} ms;"
                 f" {int(np.sum(steps > 1.05 * p50))} steps over 1.05 x p50,"
                 f" all time over p50 {over.sum():.1f} ms "
                 f"({100 * over.sum() / steps.sum():.2f}% of the window);"
                 f" loss {losses[0]:.3f} -> {losses[-1]:.3f}")
        return self.checks

    def trace_checks(self, reduced):
        """In a traced run: the kernels ran on the device as Mosaic calls,
        and flash attention did the configuration's work on every device:
        both passes, on the local shard, and of forward calls no fewer than
        a step REQUIRES (``flops.flash_passes_a_step``: one a layer
        application) and no more than a step that recomputes every
        ``ht.remat()`` group whole makes (``attention_layers``).  How many
        it makes in between is the program's.  Flash is held by the pass its
        events name and the size of what the forward pass writes, not by how
        many kernels carry a pass out or by the order of their dimensions."""
        from math import prod
        from . import flops, trace_reduce as tr
        p = self.p
        keys = {k for ev in reduced["devices"].values() for _, _, k in ev}
        missing = [n for n in p.KERNELS if not any(n in k for k in keys)]
        want = p.expected_kernel_shapes()
        lo, hi = tr.window_of(reduced["host"], STEP_SPANS)
        fwd_name, bwd_name = (flops.FLASH_PASSES[n]["events"]
                              for n in ("forward", "backward"))
        fwd = tr.events_holding(reduced, lo, hi, fwd_name)
        bwd = tr.events_holding(reduced, lo, hi, bwd_name)
        without = sorted(d for d in fwd if not fwd[d] or not bwd[d])
        dtype = tr.HLO_DTYPES[want["compute_dtype"]]
        wrote = {tr.first_result(k, fwd_name)
                 for ev in fwd.values() for _, _, k in ev}
        off_shard = [w for w in wrote if w is None or w[0] != dtype
                     or prod(w[1]) != want["flash_elements"]]

        def shown(results):
            return sorted("unreadable" if w is None else
                          f"{w[0]}{list(w[1])}" for w in results)
        steps = tr.count_spans(reduced["host"], STEP_SPANS)
        calls = {d: len(ev) for d, ev in fwd.items()}
        required = flops.flash_passes_a_step(want)
        least, most = required * steps, want["attention_layers"] * steps
        counts = set(calls.values())
        a_pass = sorted(round(n / least, 4) for n in counts)
        return [(not missing, f"the device ran {p.KERNELS} (missing: "
                 f"{missing})"),
                (bool(fwd) and not without,
                 f"every device of the traced window ({sorted(fwd)}) ran "
                 f"{fwd_name}* and {bwd_name}* (without one: {without})"),
                (bool(wrote) and not off_shard,
                 f"every flash forward call wrote the local shard's "
                 f"{want['flash_rows']} x {p.seq} x {want['head_dim']} = "
                 f"{want['flash_elements']} {dtype} elements in whatever "
                 f"order (saw {shown(wrote)}; not that: "
                 f"{shown(off_shard)})"),
                (len(counts) == 1 and least <= min(counts) <= most,
                 f"flash forward calls on each device {calls}: the same on "
                 f"every device, at least the {required} required pass(es) "
                 f"x {steps} traced steps = {least} and at most a whole "
                 f"recomputation's {want['attention_layers']} x {steps} = "
                 f"{most}; forward calls a required pass: "
                 f"{', '.join(map(repr, a_pass)) or 'none'}")]


LOOPS = {"train_loop": TrainLoop}
