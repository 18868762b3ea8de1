"""SLO-driven control plane: the fleet that operates itself.

PRs 4/9/10 gave the runtime eyes — queue depth, TPOT EWMAs, per-rid
timelines, per-program cost capture, incident counts — but nothing
ACTED on those signals: a bursty trace still rode FIFO-then-expire into
deadline misses while idle capacity sat undispatched.
:class:`FleetController` closes the loop.  It supervises one
:class:`~.fleet.EngineFleet` against a declared :class:`SLO` with three
actuators, all built on existing machinery:

* **autoscaling** — spawn (:meth:`~.fleet.EngineFleet.add_replica`) and
  drain (:meth:`~.fleet.EngineFleet.remove_replica`, the PR 6 drain
  path) replicas, driven by queue-depth and deadline-miss-rate EWMAs
  with hysteresis (separate up/down thresholds) and a cooldown so
  breaker flaps don't thrash scale.  Scale-down is two-phase and never
  blocks a tick: drain first, remove once drained — zero accepted-rid
  loss by construction.
* **predictive admission** — estimate each request's cost at
  ``submit()`` from measured signals (per-token decode cost ×
  ``max_new`` + bucketed prefill cost + queue wait at the best replica)
  and shed work that provably cannot meet its deadline at current load
  with a typed :class:`SLOReject` carrying the estimate, instead of
  admitting-then-expiring.  The estimator only rejects on EVIDENCE: with
  no measured decode cost yet, everything is admitted.
* **brownout degradation** — a staged degrade ladder
  (``normal → cap_max_new → shed_no_deadline → essential_only``)
  entered on sustained SLO violation once scale is exhausted and exited
  on sustained recovery.  ``essential_only`` rejects all external
  submits; failover/replay traffic re-homes through the fleet's
  internal ``_place`` path and is never throttled.  Every scale or
  degrade transition is recorded as a flight-recorder incident
  (``slo_scale`` / ``slo_degrade``) and a ``hetu_slo_*`` metric.

The controller is clock-injectable (defaults to the fleet's clock) and
drives the same way the fleet does: call :meth:`FleetController.tick`
after each ``pump()`` in manual mode, or :meth:`start` a supervisor
thread next to a threaded fleet.  ``telemetry.enable(debug=True)``
mounts :func:`slo_report` at ``/slo``.
"""

from __future__ import annotations

import threading
import time
import warnings
import weakref

from .. import telemetry as _telemetry
from .health import (DEGRADED, DISPATCHABLE, DRAINING, HEALTHY,
                     QUARANTINED, STOPPED)
from .scheduler import TERMINAL_OK

#: the brownout ladder, mildest first; the level INDEXES this tuple
DEGRADE_LEVELS = ("normal", "cap_max_new", "shed_no_deadline",
                  "essential_only")

#: controllers alive in this process, for the /slo debug endpoint
_LIVE = weakref.WeakSet()


class SLO:
    """A declared serving objective the controller steers toward.

    ``deadline_miss_target`` is the tolerated fraction of finished
    requests retiring with ``finish_reason="deadline"`` (EWMA-smoothed).
    ``ttft_p99_s`` / ``tpot_p99_s`` bound the worst replica's latency
    EWMAs (None disables the bound).  ``max_shed_fraction`` caps the
    VOLUNTARY shed rate: once the controller is shedding more than this
    fraction of offered work it stops escalating the degrade ladder —
    shedding harder cannot be the fix for an SLO that counts shed work
    against attainment."""

    def __init__(self, deadline_miss_target=0.05, ttft_p99_s=None,
                 tpot_p99_s=None, max_shed_fraction=0.25):
        if not 0.0 <= deadline_miss_target <= 1.0:
            raise ValueError(
                f"deadline_miss_target must be in [0, 1], got "
                f"{deadline_miss_target}")
        if not 0.0 <= max_shed_fraction <= 1.0:
            raise ValueError(
                f"max_shed_fraction must be in [0, 1], got "
                f"{max_shed_fraction}")
        for label, v in (("ttft_p99_s", ttft_p99_s),
                         ("tpot_p99_s", tpot_p99_s)):
            if v is not None and v <= 0:
                raise ValueError(f"{label} must be > 0, got {v}")
        self.deadline_miss_target = float(deadline_miss_target)
        self.ttft_p99_s = None if ttft_p99_s is None else float(ttft_p99_s)
        self.tpot_p99_s = None if tpot_p99_s is None else float(tpot_p99_s)
        self.max_shed_fraction = float(max_shed_fraction)

    def as_dict(self):
        return {"deadline_miss_target": self.deadline_miss_target,
                "ttft_p99_s": self.ttft_p99_s,
                "tpot_p99_s": self.tpot_p99_s,
                "max_shed_fraction": self.max_shed_fraction}

    def __repr__(self):
        return f"SLO({self.as_dict()!r})"


class SLOReject(RuntimeError):
    """A submit refused by the controller BEFORE taking a slot.

    ``reason`` is one of ``"infeasible_deadline"`` (the predictive
    estimate proves the deadline cannot be met at current load),
    ``"no_deadline_brownout"`` (deadline-less traffic shed at degrade
    level >= 2), or ``"essential_only"`` (level 3 rejects all external
    work).  ``estimate`` carries the admission cost breakdown (seconds:
    ``wait_s``/``prefill_s``/``decode_s``/``total_s``/``slack_s``) when
    the rejection was estimate-driven, else None.  ``degrade_level``
    is the ladder level at rejection time."""

    def __init__(self, reason, estimate=None, degrade_level=0):
        self.reason = str(reason)
        self.estimate = estimate
        self.degrade_level = int(degrade_level)
        detail = ""
        if estimate is not None:
            detail = (f" (need {estimate['total_s']:.3f}s, have "
                      f"{estimate['slack_s']:.3f}s)")
        super().__init__(
            f"shed by SLO controller: {self.reason}"
            f"[level={DEGRADE_LEVELS[self.degrade_level]}]{detail}")


class CostModel:
    """Measured request-cost estimator for predictive admission.

    ``decode_s`` is an EWMA of seconds per generated token, fed from the
    fleet's per-replica TPOT EWMAs every tick (the best replica's —
    admission must only shed work that cannot meet its deadline even on
    the FASTEST path).  Prefill cost is bucketed by power-of-two prompt
    length (measured ``ttft - queue_wait`` per finished request, the
    PR 10 signal shape); an unseen bucket borrows the nearest measured
    one, and with no prefill evidence at all one decode step stands in.
    :meth:`prime` seeds ``decode_s`` from a
    :class:`~..telemetry.profiling.ProgramProfiler` observed profile so
    a controller can start warm from a prior ``--profile`` round.

    The governing principle: estimates only ever REJECT work when built
    on measurement — ``estimate()`` returns ``total_s=None`` (admit)
    until a decode cost exists."""

    def __init__(self, alpha=0.3):
        self.alpha = float(alpha)
        self.decode_s = None      # EWMA seconds / generated token
        self.prefill_s = {}       # pow2 bucket -> EWMA seconds
        # speculative decoding divisor: measured accepted tokens per
        # verify step (None until a speculating engine reports) — one
        # decode DISPATCH commits this many tokens, so per-token cost
        # derived from per-step timings must divide by it
        self.accepted_per_step = None

    @staticmethod
    def bucket(prompt_len):
        return max(1, int(prompt_len)).bit_length()

    def _fold(self, old, sample):
        s = float(sample)
        return s if old is None else \
            (1.0 - self.alpha) * old + self.alpha * s

    def observe_decode(self, seconds):
        if seconds is not None and seconds > 0:
            self.decode_s = self._fold(self.decode_s, seconds)

    def observe_speculation(self, accepted_per_step):
        """Fold a speculating engine's measured accepted-tokens-per-
        verify-step (the engine's own acceptance EWMA).  Clamped to
        >= 1: even a fully-rejecting window commits one token."""
        if accepted_per_step is not None and accepted_per_step > 0:
            self.accepted_per_step = self._fold(
                self.accepted_per_step, max(1.0, accepted_per_step))

    def observe_prefill(self, prompt_len, seconds):
        if seconds is None or seconds < 0:
            return
        b = self.bucket(prompt_len)
        self.prefill_s[b] = self._fold(self.prefill_s.get(b), seconds)

    def prefill_estimate(self, prompt_len):
        """Measured bucket, else the nearest measured bucket (larger
        preferred — conservative), else None."""
        if not self.prefill_s:
            return None
        b = self.bucket(prompt_len)
        if b in self.prefill_s:
            return self.prefill_s[b]
        near = min(self.prefill_s,
                   key=lambda k: (abs(k - b), -k))
        return self.prefill_s[near]

    def prime(self, profiler, decode="serve_decode"):
        """Seed ``decode_s`` from an OBSERVED program profile (one with
        measured ``steps_per_sec`` in its derived block).  Profiled
        steps are verify DISPATCHES: under speculative decoding each
        commits ``accepted_per_step`` tokens, so the per-token seed
        divides by the measured acceptance when one is known."""
        prof = profiler.profile(decode)
        derived = (prof or {}).get("derived") or {}
        sps = derived.get("steps_per_sec")
        if sps:
            per_step = 1.0 / float(sps)
            if self.accepted_per_step:
                per_step /= self.accepted_per_step
            self.observe_decode(per_step)
        return self.decode_s

    def as_dict(self):
        return {"decode_s": self.decode_s,
                "prefill_s": {f"2^{k}": v
                              for k, v in sorted(self.prefill_s.items())},
                "accepted_per_step": self.accepted_per_step,
                "alpha": self.alpha}


class FleetController:
    """Feedback controller steering one EngineFleet toward its SLO.

    Route external traffic through :meth:`submit` (predictive admission
    + the degrade ladder) and call :meth:`tick` once per pump/interval
    (sense → learn costs → scale → degrade).  ``min_engines`` /
    ``max_engines`` bound autoscaling; ``scale_up_queue`` /
    ``scale_down_queue`` are per-replica queue-depth thresholds with
    hysteresis (down << up); ``cooldown_s`` spaces scale actions so a
    breaker flap (quarantine → restart) cannot thrash scale;
    ``degrade_enter_ticks`` / ``degrade_exit_ticks`` are the sustained
    violation/recovery runs required to move the ladder.  All tunables
    are documented in docs/SLO.md."""

    def __init__(self, fleet, slo=None, *, clock=None, cost_model=None,
                 min_engines=1, max_engines=4,
                 scale_up_queue=4.0, scale_down_queue=0.5,
                 cooldown_s=2.0, ewma_alpha=0.3,
                 degrade_enter_ticks=10, degrade_exit_ticks=20,
                 brownout_max_new=16, admission_margin=1.0,
                 hbm_limit_bytes=None, hbm_safety=0.9,
                 mfu_scale_threshold=None, rebalance_ratio=None,
                 rebalance_cooldown_s=None, planner=None, alerts=None):
        if min_engines < 1:
            raise ValueError(
                f"min_engines must be >= 1, got {min_engines}")
        if max_engines < min_engines:
            raise ValueError(
                f"max_engines={max_engines} < min_engines={min_engines}")
        self.fleet = fleet
        self.slo = slo if slo is not None else SLO()
        self.name = fleet.name
        self._clock = clock if clock is not None else fleet._clock
        self.cost = cost_model if cost_model is not None else CostModel(
            alpha=ewma_alpha)
        self.min_engines = int(min_engines)
        self.max_engines = int(max_engines)
        self.scale_up_queue = float(scale_up_queue)
        self.scale_down_queue = float(scale_down_queue)
        self.cooldown_s = float(cooldown_s)
        self.ewma_alpha = float(ewma_alpha)
        self.degrade_enter_ticks = int(degrade_enter_ticks)
        self.degrade_exit_ticks = int(degrade_exit_ticks)
        self.brownout_max_new = int(brownout_max_new)
        self.admission_margin = float(admission_margin)
        # direction-5 memory/compute inputs: the HbmLedger's tracked
        # bytes vs device capacity gate scale-up (a replica whose KV
        # pool won't fit must not be added just to crash), and measured
        # MFU (ProgramProfiler.observe) reads as compute saturation
        self.hbm_limit_bytes = (None if hbm_limit_bytes is None
                                else int(hbm_limit_bytes))
        self.hbm_safety = float(hbm_safety)
        self.mfu_scale_threshold = (None if mfu_scale_threshold is None
                                    else float(mfu_scale_threshold))
        # opt-in decode-slot rebalancing: when one replica's observed
        # TPOT runs ratio× the fastest sibling's, live-migrate a stream
        # off it (None disables; the default controller never perturbs
        # placement behind the operator's back)
        if rebalance_ratio is not None and float(rebalance_ratio) <= 1.0:
            raise ValueError(
                f"rebalance_ratio must be > 1.0 (a hot/cold TPOT "
                f"ratio), got {rebalance_ratio}")
        self.rebalance_ratio = (None if rebalance_ratio is None
                                else float(rebalance_ratio))
        self.rebalance_cooldown_s = (
            float(cooldown_s) if rebalance_cooldown_s is None
            else float(rebalance_cooldown_s))
        self.hbm_headroom = None
        self.mfu = None
        self.hbm_blocked = 0
        # opt-in fleet replanning: a callable ``planner(ctl) -> fleet
        # plan dict | None`` invoked on HBM-blocked or SLO-violating
        # ticks (cooldown-spaced); whatever it returns is adopted via
        # :meth:`replan`.  ``hetu_tpu.planner.fleet_plan_from_controller``
        # is the intended implementation
        self._planner = planner
        self.replans = 0
        self._last_replan = None
        # opt-in trend input: an ``telemetry.alerts.AlertManager`` the
        # controller polls each tick (driving its TimeSeriesStore on
        # the controller's own cadence — no collector thread); firing
        # rules join _violations() as ``alert:<rule>`` entries, so
        # burn-rate pages apply scale/brownout pressure next to the
        # single-tick EWMAs
        self._alerts = alerts
        # controller state
        self.level = 0
        self.queue_ewma = None
        self.miss_ewma = None
        self.ticks = 0
        self.accepted = 0
        self.shed = 0
        self.capped = 0
        self.scale_ups = 0
        self.scale_downs = 0
        self.rebalances = 0
        self._last_rebalance = None
        self.degrade_entries = 0
        self.degrade_exits = 0
        self.max_level_seen = 0
        self._draining = set()
        self._last_scale = None
        self._last_fin = 0
        self._last_miss = 0
        self._viol_ticks = 0
        self._ok_ticks = 0
        self._viol_now = ()
        self._depth = 0
        self._rec_seen = {}       # (replica, incarnation) -> records idx
        self._thread = None
        self._running = False
        reg = _telemetry.get_registry()

        def _g(name, help):
            return reg.gauge(name, help,
                             labels=("controller",)).labels(
                                 controller=self.name)

        self._m_level = _g(
            "hetu_slo_degrade_level",
            "Brownout ladder level (0 normal, 1 cap_max_new, "
            "2 shed_no_deadline, 3 essential_only)")
        self._m_engines = _g(
            "hetu_slo_engines",
            "Live (non-draining) replicas under the controller")
        self._m_miss = _g(
            "hetu_slo_deadline_miss_ewma",
            "EWMA fraction of finished requests that missed their "
            "deadline")
        self._m_queue = _g(
            "hetu_slo_queue_depth_ewma",
            "EWMA of fleet-wide queued + running requests")
        self._m_shed_frac = _g(
            "hetu_slo_shed_fraction",
            "Fraction of offered requests shed by predictive admission "
            "or brownout")
        self._m_attain = _g(
            "hetu_slo_attainment",
            "Fraction of offered work (finished + shed) that completed "
            "healthily (eos/max_new)")
        self._m_headroom = _g(
            "hetu_slo_hbm_headroom",
            "Usable device HBM headroom in bytes (safety-scaled device "
            "capacity minus HbmLedger live bytes) seen by the "
            "controller's scale gate")
        self._m_scale = reg.counter(
            "hetu_slo_scale_events_total",
            "Autoscale actions taken by the controller",
            labels=("controller", "direction"))
        self._m_degrade = reg.counter(
            "hetu_slo_degrade_transitions_total",
            "Degrade-ladder transitions, by destination level",
            labels=("controller", "to"))
        self._m_rejects = reg.counter(
            "hetu_slo_admission_rejects_total",
            "Submits shed with SLOReject before taking a slot",
            labels=("controller", "reason"))
        self._m_replans = reg.counter(
            "hetu_plan_fleet_replans_total",
            "Planner-emitted fleet shapes adopted live via replan()",
            labels=("controller",))
        self._fl = _telemetry.get_flight()
        self._m_level.set(0)
        self._m_engines.set(len(fleet._replicas))
        _LIVE.add(self)

    # -- admission ---------------------------------------------------------
    def _reject(self, reason, estimate=None):
        self.shed += 1
        self._m_rejects.labels(controller=self.name, reason=reason).inc()
        self._m_shed_frac.set(self.shed_fraction())
        raise SLOReject(reason, estimate=estimate,
                        degrade_level=self.level)

    def estimate(self, prompt_len, max_new, now=None):
        """Admission-time cost estimate (seconds): best-replica queue
        wait + bucketed prefill + ``max_new`` decode steps.  Returns
        ``total_s=None`` when there is no measured decode cost yet —
        no evidence, no rejection."""
        now = self._clock() if now is None else now
        decode_s = self.cost.decode_s
        if decode_s is None:
            return {"wait_s": None, "prefill_s": None, "decode_s": None,
                    "total_s": None}
        wait = self._wait_estimate(decode_s)
        prefill = self.cost.prefill_estimate(prompt_len)
        if prefill is None:
            prefill = decode_s      # one step stands in
        total = wait + prefill + float(max_new) * decode_s
        return {"wait_s": wait, "prefill_s": prefill,
                "decode_s": decode_s, "total_s": total}

    def _wait_estimate(self, decode_s):
        """Expected queue wait on the BEST dispatchable replica: its
        outstanding token debt spread over its slots, at its observed
        decode rate."""
        best = None
        for rep in list(self.fleet._replicas):
            if not rep.health.dispatchable or rep.engine is None:
                continue
            b = rep.engine.scheduler.backlog()
            tpot = rep.tpot_ewma or decode_s
            slots = rep.engine.cache.n_slots
            debt = b["queued_tokens"] + b["running_tokens"]
            w = (debt / max(1, slots)) * tpot
            best = w if best is None else min(best, w)
        return 0.0 if best is None else best

    def submit(self, prompt, max_new, stream=None, eos_id=None,
               ttl=None, deadline=None, hedge=False):
        """Admit one external request through the degrade ladder and
        predictive admission, then route it via ``fleet.submit``.
        Raises :class:`SLOReject` (shed, no slot taken), or whatever
        ``fleet.submit`` raises once admitted."""
        now = self._clock()
        if ttl is not None:
            if deadline is not None:
                raise ValueError("pass ttl= or deadline=, not both")
            if ttl <= 0:
                raise ValueError(f"ttl must be > 0, got {ttl}")
            deadline = now + float(ttl)
        level = self.level
        if level >= 3:
            self._reject("essential_only")
        if level >= 2 and deadline is None:
            self._reject("no_deadline_brownout")
        eff_max_new = int(max_new)
        if level >= 1 and eff_max_new > self.brownout_max_new:
            eff_max_new = self.brownout_max_new
            self.capped += 1
        if deadline is not None:
            # prefix-cache-aware prefill: pages already interned on some
            # live replica are mapped at admission, not recomputed, so
            # the deadline estimate buckets only the uncached tail
            plen = _prompt_len(prompt)
            cached = 0
            for rep in self._live_replicas():
                pc = getattr(rep.engine, "prefix_cache", None)
                if pc is not None:
                    cached = max(cached, pc.hit_tokens(prompt))
            est = self.estimate(max(plen - cached, 1), eff_max_new,
                                now=now)
            if est["total_s"] is not None:
                slack = deadline - now
                est["slack_s"] = slack
                if est["total_s"] * self.admission_margin > slack:
                    self._reject("infeasible_deadline", estimate=est)
        freq = self.fleet.submit(prompt, eff_max_new, stream=stream,
                                 eos_id=eos_id, deadline=deadline,
                                 hedge=hedge)
        self.accepted += 1
        self._m_shed_frac.set(self.shed_fraction())
        return freq

    # -- sensing helpers ---------------------------------------------------
    def shed_fraction(self):
        offered = self.accepted + self.shed
        return self.shed / offered if offered else 0.0

    def _live_replicas(self):
        return [r for r in list(self.fleet._replicas)
                if r.health.state not in (DRAINING, STOPPED)]

    def _learn_costs(self):
        """Fold the fleet's measured signals into the cost model: the
        best replica TPOT becomes the decode cost, and every newly
        finished request's ``ttft - queue_wait`` becomes a prefill
        sample for its prompt-length bucket."""
        best = None
        for rep in list(self.fleet._replicas):
            if rep.tpot_ewma:
                best = rep.tpot_ewma if best is None \
                    else min(best, rep.tpot_ewma)
            eng = rep.engine
            if eng is None:
                continue
            # speculation-aware decode costs: TPOT EWMAs above already
            # reflect multi-token verify steps, but profiler-primed
            # per-step seeds need the measured divisor too
            aps = getattr(eng, "spec_accepted_per_step", None)
            if aps is not None:
                self.cost.observe_speculation(aps)
            key = (rep.name, rep.incarnation)
            seen = self._rec_seen.get(key, 0)
            recs = eng.records
            for rec in recs[seen:]:
                ttft = rec.get("ttft")
                qw = rec.get("queue_wait")
                pl = rec.get("prompt_len")
                if ttft is not None and qw is not None and pl:
                    self.cost.observe_prefill(
                        pl, max(0.0, ttft - qw))
            self._rec_seen[key] = len(recs)
        if best is not None:
            self.cost.observe_decode(best)

    def _device_hbm_limit(self):
        if self.hbm_limit_bytes is not None:
            return self.hbm_limit_bytes
        from ..platform import device_memory_limit
        return device_memory_limit()

    def _sense_capacity(self):
        """Fold the telemetry plane's memory/compute evidence into the
        controller: HBM headroom (safety-scaled device capacity minus
        the ledger's live bytes) and the best measured MFU across
        captured program profiles (only ``observe``-d profiles carry
        one)."""
        led = _telemetry.get_hbm_ledger()
        headroom = (self.hbm_safety * self._device_hbm_limit()
                    - led.live_bytes())
        self.hbm_headroom = float(headroom)
        self._m_headroom.set(self.hbm_headroom)
        best = None
        for prof in _telemetry.get_profiler().profiles().values():
            mfu = (prof.get("derived") or {}).get("mfu")
            if mfu is not None:
                best = mfu if best is None else max(best, mfu)
        self.mfu = best

    def _kv_projection(self):
        """Projected kv_cache bytes ONE more replica would pin: the
        per-replica mean of the pool's live bytes (every replica of one
        fleet builds the same slot geometry)."""
        led = _telemetry.get_hbm_ledger()
        kv = led.live_bytes("kv_cache")
        n = sum(1 for r in self._live_replicas() if r.engine is not None)
        return kv / n if n else 0.0

    def _hbm_would_block(self):
        """True when one more replica's projected kv_cache pool exceeds
        the current headroom — scale-up is unavailable regardless of
        max_engines, and the degrade ladder must carry the pressure."""
        projected = self._kv_projection()
        return (self.hbm_headroom is not None and projected > 0
                and projected > self.hbm_headroom)

    def _violations(self):
        out = []
        if (self.miss_ewma or 0.0) > self.slo.deadline_miss_target:
            out.append("deadline_miss")
        live = self._live_replicas()
        if self._depth == 0:
            # the replica TTFT/TPOT EWMAs are finish-time signals: with
            # nothing in flight they go stale, and holding a brownout on
            # a stale reading would wedge the ladder open forever — an
            # idle fleet meets its latency bounds by definition
            return tuple(out)
        if self.slo.ttft_p99_s is not None:
            worst = max((r.ttft_ewma for r in live if r.ttft_ewma),
                        default=None)
            if worst is not None and worst > self.slo.ttft_p99_s:
                out.append("ttft")
        if self.slo.tpot_p99_s is not None:
            worst = max((r.tpot_ewma for r in live if r.tpot_ewma),
                        default=None)
            if worst is not None and worst > self.slo.tpot_p99_s:
                out.append("tpot")
        return tuple(out)

    # -- the control loop --------------------------------------------------
    def tick(self):
        """One sense → learn → actuate pass.  Call after each
        ``fleet.pump()`` in manual mode; the :meth:`start` thread calls
        it on an interval for threaded fleets."""
        now = self._clock()
        self.ticks += 1
        self._learn_costs()
        live = self._live_replicas()
        depth = 0
        for rep in live:
            if rep.engine is not None:
                sch = rep.engine.scheduler
                depth += len(sch.queue) + len(sch.running)
        a = self.ewma_alpha
        self.queue_ewma = float(depth) if self.queue_ewma is None else \
            (1.0 - a) * self.queue_ewma + a * depth
        # deadline-miss rate from the fleet's O(1) finish counters; a
        # tick with no finishes carries no signal UNLESS the fleet is
        # idle (an idle fleet meets its SLO by definition — this is the
        # recovery path out of a brownout once traffic stops)
        fin = sum(self.fleet.finish_counts.values())
        miss = self.fleet.finish_counts.get("deadline", 0)
        dfin, dmiss = fin - self._last_fin, miss - self._last_miss
        self._last_fin, self._last_miss = fin, miss
        sample = None
        if dfin > 0:
            sample = dmiss / dfin
        elif depth == 0:
            sample = 0.0
        if sample is not None:
            self.miss_ewma = sample if self.miss_ewma is None else \
                (1.0 - a) * self.miss_ewma + a * sample
        self._depth = depth
        self._sense_capacity()
        self._reap_draining()
        viol = self._violations()
        if self._alerts is not None:
            viol += tuple(f"alert:{r}" for r in self._alerts.poll(now))
        self._viol_now = viol
        self._maybe_replan(now, viol)
        self._autoscale(now, viol)
        self._degrade(now, viol)
        self._rebalance(now)
        # refresh the live gauges
        self._m_engines.set(len(self._live_replicas()))
        self._m_miss.set(self.miss_ewma or 0.0)
        self._m_queue.set(self.queue_ewma or 0.0)
        self._m_shed_frac.set(self.shed_fraction())
        self._m_attain.set(self.attainment())
        return self

    def _cool(self, now):
        return (self._last_scale is not None
                and now - self._last_scale < self.cooldown_s)

    def _autoscale(self, now, viol):
        live = self._live_replicas()
        n = len(live)
        pressure = (bool(viol)
                    or (self.queue_ewma or 0.0)
                    > self.scale_up_queue * max(1, n)
                    # compute-saturated: measured MFU above the
                    # threshold means the device, not the queue, is the
                    # bottleneck — more replicas is the only lever
                    or (self.mfu_scale_threshold is not None
                        and (self.mfu or 0.0) > self.mfu_scale_threshold))
        if pressure and n < self.max_engines and not self._cool(now):
            if self._hbm_would_block():
                # headroom-blocked: one more replica's kv_cache pool
                # would not fit the device — scaling up would trade an
                # SLO violation for an OOM.  Degrade handles pressure.
                self.hbm_blocked += 1
                # cooldown applies to the BLOCK too: sustained pressure
                # must not emit an incident per tick
                self._last_scale = now
                self._m_scale.labels(controller=self.name,
                                     direction="up_blocked_hbm").inc()
                self._fl.incident(
                    "slo_scale", health=self.fleet.health(),
                    extra={"controller": self.name,
                           "direction": "up_blocked_hbm",
                           "n_engines": n,
                           "projected_kv_bytes": int(
                               self._kv_projection()),
                           "hbm_headroom": int(self.hbm_headroom),
                           "violations": list(viol)})
                return
            name = self.fleet.add_replica()
            self._last_scale = now
            self.scale_ups += 1
            self._scale_event("up", name, now, viol)
            return
        calm = (not viol and self.level == 0
                and (self.queue_ewma or 0.0)
                < self.scale_down_queue * max(1, n)
                and (self.miss_ewma or 0.0)
                <= self.slo.deadline_miss_target / 2.0)
        if calm and n > self.min_engines and not self._cool(now):
            victim = self._scale_down_victim(live)
            if victim is None:
                return
            # migrate-then-drain: the victim's long decode tail moves
            # to surviving siblings NOW (live KV page migration), so
            # the two-phase removal isn't gated on its slowest stream;
            # anything non-migratable just drains out as before
            self.fleet.drain(victim.name, wait=False, migrate=True)
            self._draining.add(victim.name)
            self._last_scale = now
            self.scale_downs += 1
            self._scale_event("down", victim.name, now, viol)

    def _rebalance(self, now):
        """Decode-slot rebalancing (opt-in via ``rebalance_ratio=``):
        when the hottest replica's observed TPOT runs ``ratio``× the
        fastest sibling's — thermal throttle, noisy neighbor — one
        running stream is live-migrated off it per pass (bounded,
        cooldown-spaced) instead of waiting for the health machine to
        call the replica sick.  Queue pressure counts too: a replica
        that is both slow and loaded sheds first."""
        if self.rebalance_ratio is None:
            return
        if (self._last_rebalance is not None
                and now - self._last_rebalance
                < self.rebalance_cooldown_s):
            return
        cands = [r for r in self._live_replicas()
                 if r.health.state in DISPATCHABLE and r.tpot_ewma
                 and r.name not in self._draining]
        if len(cands) < 2:
            return
        # hottest by observed decode latency, load as the tie-break
        hot = max(cands, key=lambda r: (r.tpot_ewma, len(r.inflight)))
        cool = min(r.tpot_ewma for r in cands if r is not hot)
        if hot.tpot_ewma < self.rebalance_ratio * cool \
                or not hot.inflight:
            return
        moved = self.fleet.rebalance(hot.name, max_requests=1)
        if moved:
            self.rebalances += moved
            self._last_rebalance = now

    def _scale_down_victim(self, live):
        cands = [r for r in live
                 if r.health.state in DISPATCHABLE
                 and r.name not in self._draining]
        if len(cands) <= self.min_engines:
            return None
        return min(cands, key=lambda r: (len(r.inflight),
                                         -r.index))

    def _scale_event(self, direction, engine, now, viol):
        self._m_scale.labels(controller=self.name,
                             direction=direction).inc()
        self._fl.incident(
            "slo_scale", health=self.fleet.health(),
            extra={"controller": self.name, "direction": direction,
                   "engine": engine,
                   "n_engines": len(self._live_replicas()),
                   "queue_ewma": round(self.queue_ewma or 0.0, 4),
                   "miss_ewma": round(self.miss_ewma or 0.0, 4),
                   "violations": list(viol)})

    # -- fleet replanning --------------------------------------------------
    def _maybe_replan(self, now, viol):
        """Invoke the configured ``planner=`` callable on HBM-blocked
        or SLO-violating ticks (cooldown-spaced — the spacing applies
        to the ATTEMPT, so a planner with no feasible answer is not
        hammered every tick); any plan it returns is adopted through
        :meth:`replan`."""
        if self._planner is None:
            return
        if not (viol or self._hbm_would_block()):
            return
        if (self._last_replan is not None
                and now - self._last_replan < self.cooldown_s):
            return
        self._last_replan = now
        try:
            plan = self._planner(self)
        except Exception as e:   # planner failure must not kill tick
            warnings.warn(
                f"slo controller {self.name}: planner failed "
                f"{type(e).__name__}: {e}")
            return
        if plan:
            self.replan(plan)

    def replan(self, plan):
        """Adopt a planner-emitted fleet plan live — the actuator for
        ``hetu_tpu.planner.plan_fleet`` output (a ``hetu_fleet_plan``
        dict or just its ``shape`` block).

        Page-geometry changes update the fleet's shared engine kwargs
        and ROLLING-REPLACE every live replica: the freshly-geometried
        replicas are added FIRST, then the stale ones drain out with
        live KV page migration (the PR 17 machinery), so no accepted
        request is lost.  Pure count changes add replicas or drain the
        autoscaler's victims.  ``tp_size`` cannot change on a live
        fleet (tp sub-meshes are built at construction) — a mismatch is
        recorded in the report's notes, never silently applied.  The
        target replica count is clamped to ``[min_engines,
        max_engines]``.  Returns the adoption report."""
        shape = plan.get("shape", plan)
        live = [r for r in self._live_replicas()
                if r.name not in self._draining]
        target = int(shape.get("replicas", len(live)))
        clamped = max(self.min_engines, min(self.max_engines, target))
        notes = []
        if clamped != target:
            notes.append(f"replicas {target} clamped to {clamped} "
                         f"(min={self.min_engines}, "
                         f"max={self.max_engines})")
        fleet = self.fleet
        tp_now = int(getattr(fleet, "tp_size", 1))
        tp_want = int(shape.get("tp_size", tp_now))
        if tp_want != tp_now:
            notes.append(f"tp_size {tp_now} -> {tp_want} requires a "
                         f"fleet rebuild; keeping tp={tp_now}")
        geom = {}
        for key in ("page_len", "n_pages", "n_slots", "max_len"):
            want = shape.get(key)
            if want is None:
                continue
            cur = fleet._ekw.get(key)
            if cur is not None and int(cur) != int(want):
                geom[key] = int(want)
        added, removed = [], []
        if geom and not fleet._ekw.get("paged"):
            notes.append(f"geometry change {geom} ignored: engines are "
                         f"not paged")
            geom = {}
        if geom:
            fleet._ekw.update(geom)
            for _ in range(clamped):
                added.append(fleet.add_replica())
            for rep in live:
                fleet.drain(rep.name, wait=False, migrate=True)
                self._draining.add(rep.name)
                removed.append(rep.name)
        else:
            n = len(live)
            while n < clamped:
                added.append(fleet.add_replica())
                n += 1
            while n > clamped:
                victim = self._scale_down_victim(
                    [r for r in live if r.name not in removed])
                if victim is None:
                    notes.append(f"stopped at {n} replicas: no "
                                 f"drainable victim")
                    break
                fleet.drain(victim.name, wait=False, migrate=True)
                self._draining.add(victim.name)
                removed.append(victim.name)
                n -= 1
        # adopting a shape IS a scale action: cooldown keeps the
        # autoscaler from fighting the plan on the very next tick
        self._last_scale = self._clock()
        self.replans += 1
        self._m_replans.labels(controller=self.name).inc()
        report = {"adopted": True, "target_replicas": clamped,
                  "tp_size": tp_now, "added": added,
                  "draining": removed, "geometry": geom,
                  "notes": notes}
        self._fl.incident(
            "slo_replan", health=fleet.health(),
            extra={"controller": self.name, **report,
                   "n_engines": len(self._live_replicas())})
        return report

    def _reap_draining(self):
        """Finish two-phase scale-downs: remove replicas whose drain
        completed; re-drain any that a breaker restart revived."""
        for name in sorted(self._draining):
            rep = self.fleet._by_name(name)
            if rep is None:
                self._draining.discard(name)
                continue
            st = rep.health.state
            if st in (STOPPED, QUARANTINED):
                if self.fleet.remove_replica(name, wait=False):
                    self._draining.discard(name)
            elif st in (HEALTHY, DEGRADED):
                # auto_restart revived it mid-drain: drain again
                self.fleet.drain(name, wait=False)

    def _degrade(self, now, viol):
        # "can't scale" includes HBM-blocked below max_engines: the
        # ladder must carry the pressure when adding a replica would OOM
        at_max = (len(self._live_replicas()) >= self.max_engines
                  or self._hbm_would_block())
        if viol and at_max:
            self._viol_ticks += 1
            self._ok_ticks = 0
        elif not viol:
            self._ok_ticks += 1
            self._viol_ticks = 0
        else:
            # violating but scale-up is still available: let
            # autoscaling fix it before shedding anything
            self._viol_ticks = 0
        if (self._viol_ticks >= self.degrade_enter_ticks
                and self.level < len(DEGRADE_LEVELS) - 1
                and self.shed_fraction() <= self.slo.max_shed_fraction):
            self._set_level(self.level + 1, ",".join(viol))
            self._viol_ticks = 0
        elif self._ok_ticks >= self.degrade_exit_ticks and self.level > 0:
            self._set_level(self.level - 1, "recovered")
            self._ok_ticks = 0

    def _set_level(self, level, reason):
        old, self.level = self.level, int(level)
        if self.level > old:
            self.degrade_entries += 1
        else:
            self.degrade_exits += 1
        self.max_level_seen = max(self.max_level_seen, self.level)
        self._m_level.set(self.level)
        self._m_degrade.labels(controller=self.name,
                               to=DEGRADE_LEVELS[self.level]).inc()
        self._fl.incident(
            "slo_degrade", health=self.fleet.health(),
            extra={"controller": self.name,
                   "from": DEGRADE_LEVELS[old],
                   "to": DEGRADE_LEVELS[self.level],
                   "reason": reason,
                   "queue_ewma": round(self.queue_ewma or 0.0, 4),
                   "miss_ewma": round(self.miss_ewma or 0.0, 4),
                   "n_engines": len(self._live_replicas())})
        warnings.warn(
            f"slo controller {self.name}: degrade "
            f"{DEGRADE_LEVELS[old]} -> {DEGRADE_LEVELS[self.level]} "
            f"({reason})")

    # -- introspection -----------------------------------------------------
    def attainment(self):
        """Fraction of OFFERED work (finished + shed) that completed
        healthily (eos/max_new).  Shed and missed work both count
        against it — degrading is a controlled loss, not a free pass."""
        fc = self.fleet.finish_counts
        ok = sum(fc.get(r, 0) for r in TERMINAL_OK)
        offered = sum(fc.values()) + self.shed
        return ok / offered if offered else 1.0

    def report(self):
        """The /slo debug block: SLO, ladder position, EWMAs, cost
        model, and action counters."""
        return {
            "controller": self.name,
            "slo": self.slo.as_dict(),
            "level": self.level,
            "level_name": DEGRADE_LEVELS[self.level],
            "violations": list(self._viol_now),
            "alerts_firing": (None if self._alerts is None
                              else sorted(self._alerts.firing())),
            "n_engines": len(self._live_replicas()),
            "draining": sorted(self._draining),
            "ewma": {"queue_depth": self.queue_ewma,
                     "deadline_miss": self.miss_ewma},
            "cost_model": self.cost.as_dict(),
            "capacity": {
                "hbm_headroom": (None if self.hbm_headroom is None
                                 else int(self.hbm_headroom)),
                "projected_kv_bytes": int(self._kv_projection()),
                "mfu": self.mfu,
                "hbm_blocked": self.hbm_blocked},
            "shed_fraction": round(self.shed_fraction(), 4),
            "attainment": round(self.attainment(), 4),
            "counters": {"ticks": self.ticks,
                         "accepted": self.accepted,
                         "shed": self.shed,
                         "capped": self.capped,
                         "scale_ups": self.scale_ups,
                         "scale_downs": self.scale_downs,
                         "rebalances": self.rebalances,
                         "replans": self.replans,
                         "degrade_entries": self.degrade_entries,
                         "degrade_exits": self.degrade_exits,
                         "max_level_seen": self.max_level_seen},
        }

    # -- threaded drive ----------------------------------------------------
    def start(self, interval=0.05):
        """Run :meth:`tick` on a daemon supervisor thread (threaded
        fleets).  No-op when already running."""
        if self._running:
            return self
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, args=(float(interval),), daemon=True,
            name=f"slo-{self.name}")
        self._thread.start()
        return self

    def _loop(self, interval):
        while self._running:
            try:
                self.tick()
            except Exception as e:    # the controller must never die
                warnings.warn(
                    f"slo controller {self.name}: tick error "
                    f"{type(e).__name__}: {e}")
            time.sleep(interval)

    def stop(self):
        self._running = False
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


def _prompt_len(prompt):
    try:
        return int(getattr(prompt, "size", None) or len(prompt))
    except TypeError:
        return 1


def slo_report():
    """{controller: report} for every live FleetController — the
    ``/slo`` debug endpoint payload."""
    return {c.name: c.report() for c in list(_LIVE)}
