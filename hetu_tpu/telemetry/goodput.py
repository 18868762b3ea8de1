"""Goodput ledger: attribute wall-time x chips into exhaustive buckets.

Every chaos/fault PR (2, 5, 6, 17) proved the system RECOVERS; none
answered what the failure COST.  The ledger answers it with the PR 4
phases-sum-to-wall discipline lifted to the whole process: a window of
``wall_s * chips`` chip-seconds is attributed into buckets that sum to
1.0 BY CONSTRUCTION — measured sinks first, the residual is idle, and
when concurrent measured sinks oversubscribe the wall (threaded
serving) every measured bucket is scaled down proportionally so the
identity holds instead of silently breaking.

Buckets (:data:`GOODPUT_BUCKETS`):

* useful — ``useful_train`` (executor step time minus compile and
  guard-tripped steps), ``useful_prefill`` / ``useful_decode``
  (serving span time minus failover replay);
* lost, by mechanism — ``compile`` (the executor's ``compile`` span,
  which is program construction alone, plus XLA's own trace, lowering
  and compile or cache read as ``jax.monitoring`` reports them under
  ``run`` roots, where they are taken out of the step time, and under
  ``executor_init``: the roots' ``xla_s``, ``telemetry/tracing.py``),
  ``data_wait`` (input stall spans), ``checkpoint_save`` /
  ``checkpoint_restore`` (histograms), ``rollback`` (guard-tripped
  step time + the rollback-restore span), ``failover_replay``
  (replayed tokens x measured per-token decode cost, carved out of the
  serving spans), ``kv_migration`` (live-migration span), ``reshard``
  (the elastic trainer's re-plan + rebuild + resharded-restore span —
  what a capacity change costs end to end),
  ``brownout_shed`` (shed requests x measured mean request cost,
  bounded by the idle residual — capacity we chose not to spend),
  ``idle`` (the residual).

Everything is fed from EXISTING spans/counters — no new probes in hot
paths; the only new spans this PR adds are ``compile`` (executor
program build), ``rollback_restore`` (guard), and ``kv_migrate``
(fleet), each on an already-cold path.  Per-trainer / per-replica
attribution rides the label sets the counters already carry: the
report splits ``useful_train`` by subgraph step-time share and
``useful_decode`` by scheduler token share.

Disabled by default like every PR 4 instrument: :meth:`begin` /
:meth:`account` while disabled are one flag check (<20 us/op, pinned
by ``tests/test_timeseries.py``).
"""

from __future__ import annotations

import time

__all__ = ["GoodputLedger", "GOODPUT_BUCKETS", "USEFUL_BUCKETS",
           "LOST_CAUSES"]

#: every bucket the ledger can attribute chip-time to (fractions sum to 1)
GOODPUT_BUCKETS = ("useful_train", "useful_prefill", "useful_decode",
                   "compile", "data_wait", "checkpoint_save",
                   "checkpoint_restore", "rollback", "failover_replay",
                   "kv_migration", "reshard", "brownout_shed", "idle")

USEFUL_BUCKETS = ("useful_train", "useful_prefill", "useful_decode")

#: the lost-capacity causes (everything that is not useful or idle)
LOST_CAUSES = tuple(b for b in GOODPUT_BUCKETS
                    if b not in USEFUL_BUCKETS)


def _csum(snap, name):
    m = snap.get(name)
    if m is None:
        return 0.0
    return float(sum(s["value"] for s in m["samples"]))


def _hsum(snap, name):
    m = snap.get(name)
    if m is None:
        return 0.0
    return float(sum(s["sum"] for s in m["samples"]))


def _hcount(snap, name):
    m = snap.get(name)
    if m is None:
        return 0
    return int(sum(s["count"] for s in m["samples"]))


def _by_label(snap, name, field="value"):
    """{label_str: value} per series of one metric."""
    m = snap.get(name)
    if m is None:
        return {}
    out = {}
    for s in m["samples"]:
        key = ",".join(f"{k}={v}" for k, v in sorted(s["labels"].items()))
        out[key] = out.get(key, 0.0) + float(s[field])
    return out


class GoodputLedger:
    """Windowed chip-time attribution over the process registry+tracer.

    :meth:`begin` pins the window start (a cumulative-sink baseline);
    :meth:`account` attributes everything since.  Ledgers are cheap —
    make one per trainer / replica / chaos stage for scoped windows;
    the ``name`` label keeps their gauges apart."""

    def __init__(self, registry=None, tracer=None, *, name="process",
                 chips=1, clock=None, enabled=False):
        if chips < 1:
            raise ValueError(f"chips must be >= 1, got {chips}")
        self._registry = registry
        self._tracer = tracer
        self.name = str(name)
        self.chips = int(chips)
        self.enabled = bool(enabled)
        self._clock = clock if clock is not None else time.perf_counter
        self._base = None           # (t0, sinks) window baseline
        self._m_goodput = None
        self._m_lost = None

    # -- the cumulative sinks ---------------------------------------------
    def _sinks(self):
        snap = self._registry.snapshot() if self._registry else {}
        agg = self._tracer.aggregate() if self._tracer else {}
        xla = self._tracer.xla_seconds() if self._tracer else {}

        def span(n):
            return float(agg.get(n, {}).get("total_s", 0.0))

        return {
            "train_wall": _hsum(snap, "hetu_executor_step_seconds"),
            "train_steps": _hcount(snap, "hetu_executor_step_seconds"),
            "train_by": _by_label(snap, "hetu_executor_step_seconds",
                                  field="sum"),
            "compile": span("compile"),
            # XLA's phases inside step roots (part of train_wall) and
            # inside executor_init (part of no step)
            "xla_run": float(xla.get("run", 0.0)),
            "xla_init": float(xla.get("executor_init", 0.0)),
            "data_wait": span("data_wait") + span("prefetch_h2d"),
            "ckpt_save": _hsum(snap, "hetu_checkpoint_save_seconds"),
            "restore": _hsum(snap, "hetu_checkpoint_restore_seconds"),
            "rollback_restore": span("rollback_restore"),
            "guard_trips": (_csum(snap, "hetu_guard_trips_total")
                            + _csum(snap, "hetu_guard_inner_trips_total")),
            "prefill": span("serve_prefill"),
            "decode": span("serve_decode"),
            "tokens": _csum(snap, "hetu_serving_tokens_total"),
            "tokens_by": _by_label(snap, "hetu_serving_tokens_total"),
            "replayed": _csum(snap, "hetu_serving_replayed_tokens_total"),
            "kv_migration": span("kv_migrate"),
            # the elastic recover protocol's span, plus the checkpoint
            # flush/restore it contains (those also hit the save/
            # restore histograms — carved back out in account() the
            # way rollback_restore is, so no second is counted twice)
            "reshard": span("elastic_reshard"),
            "elastic_save": span("elastic_ckpt_save"),
            "elastic_restore": span("elastic_ckpt_restore"),
            "rejections": (_csum(snap, "hetu_serving_rejections_total")
                           + _csum(snap,
                                   "hetu_slo_admission_rejects_total")),
            "finished": _csum(snap, "hetu_serving_requests_total"),
        }

    @staticmethod
    def _delta(cur, base):
        d = {}
        for k, v in cur.items():
            if isinstance(v, dict):
                b = base.get(k, {}) if base else {}
                d[k] = {kk: max(0.0, vv - b.get(kk, 0.0))
                        for kk, vv in v.items()}
            else:
                b = base.get(k, 0.0) if base else 0.0
                d[k] = max(0.0, v - b)
        return d

    # -- windowing ---------------------------------------------------------
    def begin(self, now=None):
        """Pin the attribution window start; no-op while disabled."""
        if not self.enabled:
            return None
        t = self._clock() if now is None else float(now)
        self._base = (t, self._sinks())
        return t

    # -- attribution -------------------------------------------------------
    def account(self, wall_s=None, chips=None, now=None,
                update_gauges=True):
        """Attribute the window since :meth:`begin` (or since the
        ledger was enabled) into :data:`GOODPUT_BUCKETS`.

        Returns ``{"wall_chip_s", "buckets" (seconds), "fractions"
        (sum to 1 exactly), "goodput_fraction", "lost", "replicas"}``;
        ``{"enabled": False}`` while disabled."""
        if not self.enabled:
            return {"enabled": False}
        t = self._clock() if now is None else float(now)
        if self._base is None:
            self.begin(now=t)
        t0, base = self._base
        d = self._delta(self._sinks(), base)
        wall = float(wall_s) if wall_s is not None else max(0.0, t - t0)
        chips = self.chips if chips is None else int(chips)
        cap = wall * chips

        # training: step wall minus the compile span and the XLA phases
        # it contains, minus guard-tripped steps (each trip wasted ~one
        # mean step)
        mean_step = (d["train_wall"] / d["train_steps"]
                     if d["train_steps"] else 0.0)
        in_steps = d["compile"] + d["xla_run"]
        train_pool = max(0.0, d["train_wall"] - in_steps)
        tripped = min(train_pool, d["guard_trips"] * mean_step)
        useful_train = train_pool - tripped
        # rollback = tripped step time + the measured restore span; the
        # restore HISTOGRAM also observed that span, so the plain
        # checkpoint_restore bucket is the histogram minus it (same for
        # the elastic recover protocol's flush/restore, which belong to
        # the reshard bucket)
        rollback = tripped + d["rollback_restore"]
        ckpt_restore = max(0.0, d["restore"] - d["rollback_restore"]
                           - d["elastic_restore"])
        ckpt_save = max(0.0, d["ckpt_save"] - d["elastic_save"])
        # serving: failover replay re-derives tokens that were already
        # paid for once — cost ~= replayed tokens at the measured
        # per-token decode cost, carved out of decode then prefill
        per_tok = d["decode"] / d["tokens"] if d["tokens"] > 0 else 0.0
        replay_s = min(d["decode"] + d["prefill"],
                       d["replayed"] * per_tok)
        replay_decode = min(d["decode"], replay_s)
        replay_prefill = min(d["prefill"], replay_s - replay_decode)
        useful_decode = d["decode"] - replay_decode
        useful_prefill = d["prefill"] - replay_prefill

        buckets = {
            "useful_train": useful_train,
            "useful_prefill": useful_prefill,
            "useful_decode": useful_decode,
            "compile": in_steps + d["xla_init"],
            "data_wait": d["data_wait"],
            "checkpoint_save": ckpt_save,
            "checkpoint_restore": ckpt_restore,
            "rollback": rollback,
            "failover_replay": replay_decode + replay_prefill,
            "kv_migration": d["kv_migration"],
            "reshard": d["reshard"],
            "brownout_shed": 0.0,
        }
        measured = sum(buckets.values())
        scaled = False
        if cap > 0 and measured > cap:
            # concurrent measured sinks oversubscribed the wall
            # (threaded serving): scale proportionally so the sum-to-1
            # identity survives instead of silently breaking
            f = cap / measured
            buckets = {k: v * f for k, v in buckets.items()}
            measured = cap
            scaled = True
        idle = max(0.0, cap - measured)
        # brownout shed is capacity we REFUSED to spend — it can only
        # come out of the idle residual, priced at the measured mean
        # cost of a finished request
        mean_req = ((useful_decode + useful_prefill) / d["finished"]
                    if d["finished"] > 0 else 0.0)
        shed = min(idle, d["rejections"] * mean_req)
        buckets["brownout_shed"] = shed
        idle -= shed
        buckets["idle"] = idle

        if cap > 0:
            fractions = {k: v / cap for k, v in buckets.items()}
            # the residual in FRACTION space: exact sum-to-1
            fractions["idle"] = 1.0 - sum(
                v for k, v in fractions.items() if k != "idle")
        else:
            fractions = {k: 0.0 for k in buckets}
            fractions["idle"] = 1.0
        goodput = sum(fractions[k] for k in USEFUL_BUCKETS)
        lost = {k: fractions[k] for k in LOST_CAUSES}

        if update_gauges:
            self._set_gauges(goodput, lost)
        return {"ledger": self.name,
                "wall_chip_s": round(cap, 6),
                "chips": chips,
                "window_s": round(wall, 6),
                "scaled_to_wall": scaled,
                "buckets_s": {k: round(v, 6)
                              for k, v in buckets.items()},
                "fractions": {k: round(v, 9)
                              for k, v in fractions.items()},
                "goodput_fraction": round(goodput, 9),
                "lost": {k: round(v, 9) for k, v in lost.items()},
                "replicas": self._replica_split(d, fractions)}

    def _replica_split(self, d, fractions):
        """Label-share attribution of the useful fractions: train by
        subgraph step-time share, decode by scheduler token share."""
        out = {}
        total_t = sum(d["train_by"].values())
        if total_t > 0:
            out["useful_train"] = {
                k: round(fractions["useful_train"] * v / total_t, 9)
                for k, v in d["train_by"].items()}
        total_k = sum(d["tokens_by"].values())
        if total_k > 0:
            out["useful_decode"] = {
                k: round(fractions["useful_decode"] * v / total_k, 9)
                for k, v in d["tokens_by"].items()}
        return out

    def _set_gauges(self, goodput, lost):
        reg = self._registry
        if reg is None:
            return
        if self._m_goodput is None:
            self._m_goodput = reg.gauge(
                "hetu_goodput_fraction",
                "Fraction of wall x chips spent on useful work "
                "(train steps + prefill/decode tokens) in the last "
                "accounted window", labels=("ledger",))
            self._m_lost = reg.gauge(
                "hetu_goodput_lost_fraction",
                "Fraction of wall x chips lost to one cause in the "
                "last accounted window", labels=("ledger", "cause"))
        self._m_goodput.labels(ledger=self.name).set(goodput)
        for cause, frac in lost.items():
            self._m_lost.labels(ledger=self.name, cause=cause).set(frac)

    def report_block(self):
        """The ``/goodput`` debug payload + ``telemetry.report()``
        block: the window since :meth:`begin` (telemetry.enable pins
        it), gauges untouched."""
        if not self.enabled:
            return {"enabled": False}
        return dict(self.account(update_gauges=False), enabled=True)
