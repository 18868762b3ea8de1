"""``chipbench/metrics/_phases.py`` on a hand-built run: two devices with
known gaps, the executor's spans in the program's ring on a host clock that
runs a known 5 s behind the profiler's, and every value worked out by hand.

One step of the hand-built run, in ms from the start of the benchmark's
``executor_run`` span (period 100, three traced steps)::

    executor_run  0 ........................................... 98
    run             1 ....................................... 97
    h2d               2 .. 6
    dispatch                 7 .. 10
    fetch                            11 ..................... 96
    device 0 busy                         20 .. 50   55 .. 90
    device 1 busy                              30 ......... 90
"""

import pytest

from chipbench import trace_reduce as tr
from chipbench.metrics import _phases
from hetu_tpu import telemetry

OFFSET = 5.0            # profiler clock = host clock + OFFSET
T0 = 100.0              # host time of the first traced step
PERIOD = 0.100
MAIN, OTHER = 7, 8      # thread identifiers

#: by hand, ms a step, mean of the two devices (see the module docstring):
#: device 0: h2d 4, dispatch 3 + 9 (fetch before the program's start at 20),
#: fetch 5 (gap inside the program) + 6 (after its end), run 1 + 1 + 1 + 1,
#: outside 1 + 1 and the 2 between steps twice in three steps;
#: device 1: the same but dispatch 3 + 19 and fetch 6
BY_HAND = {"h2d": 4.0, "dispatch": (12 + 22) / 2, "fetch": (11 + 6) / 2,
           "run_self": 4.0, "outside_run": (3 * 2 + 2 * 2) / 3}


def ns(t):
    return (t + OFFSET) * 1e9


def build(jitter_us=0.0, devices=True, traced_steps=3, program_spans=True):
    """``ctx`` and the ring for the run drawn above."""
    tracer = telemetry.get_tracer()
    tracer.clear()
    host_runs, host, dev0, dev1, mod0, mod1 = [], [], [], [], [], []
    if program_spans:       # set-up ran another subgraph on the same thread
        tracer._record("run", T0 - 2.0, 0.5, None, "validate:0", MAIN)
    for k in range(-10, traced_steps):      # ten steps before the trace
        t = T0 + k * PERIOD
        host_runs.append((t, t + 0.098))
        if program_spans:
            key = f"train:{k + 20}"
            for name, a, b, parent in (("h2d", 2, 6, "run"),
                                       ("dispatch", 7, 10, "run"),
                                       ("fetch", 11, 96, "run"),
                                       ("run", 1, 97, None)):
                tracer._record(name, t + a * 1e-3, (b - a) * 1e-3, parent,
                               key, MAIN)
            # another thread's span is not the run thread's phase
            tracer._record("prefetch_h2d", t + 0.012, 0.050, None, None,
                           OTHER)
        else:
            tracer._record("h2d", t + 0.002, 0.004)
            tracer._record("dispatch", t + 0.007, 0.003)
        if k < 0:
            continue
        lead = jitter_us * 1e3 if k == 1 else 0.0
        host.append((ns(t) + lead, 0.098e9, "executor_run"))
        host.append((ns(t) - 5000.0, 3000.0, "feed"))
        dev0 += [(ns(t + 0.020), 0.030e9, "fusion_a"),
                 (ns(t + 0.055), 0.035e9, "fusion_b")]
        dev1 += [(ns(t + 0.030), 0.060e9, "fusion_a")]
        # the upload's tiny cast program must not be taken for the step's
        mod0 += [(ns(t + 0.003), 1000.0, "jit_convert_element_type(1)"),
                 (ns(t + 0.020), 0.070e9, "jit_step_fn(2)")]
        mod1 += [(ns(t + 0.030), 0.060e9, "jit_step_fn(2)")]
    reduced = {"devices": {0: dev0, 1: dev1, 2: []} if devices else {},
               "modules": {0: mod0, 1: mod1} if devices else {},
               "host": sorted(host)}
    said = []
    ctx = {"rec": {"t0": T0 - 10 * PERIOD,
                   "trace_started_at": T0 - 0.5 * PERIOD},
           "spans": {"executor_run": host_runs},
           "trace": {"reduced": reduced,
                     "summary": tr.summary(reduced, ("executor_run",),
                                           ("feed", "executor_run"))},
           "registry": {
               "hetu_executor_h2d_bytes_total": {"samples": [
                   {"labels": {"subgraph": "train"}, "value": 13e6}]},
               "hetu_executor_steps_total": {"samples": [
                   {"labels": {"subgraph": "train"}, "value": 13}]}},
           "say": said.append}
    return ctx, said


@pytest.fixture(autouse=True)
def clean_ring():
    yield
    telemetry.get_tracer().clear()


@pytest.mark.parametrize("part", _phases.PARTS)
def test_each_part_as_computed_by_hand(part):
    ctx, _ = build()
    assert _phases.reader(part)(ctx) == pytest.approx(BY_HAND[part],
                                                      abs=1e-6)


def test_parts_add_up_to_the_idle_time_a_step_and_say_what_they_saw():
    ctx, said = build()
    parts = {p: _phases.reader(p)(ctx) for p in _phases.PARTS}
    summ = ctx["trace"]["summary"]
    idle_ms_a_step = (summ["window_s"] - summ["busy_s"]) * 1e3 / 3
    assert sum(parts.values()) == pytest.approx(idle_ms_a_step, rel=1e-9)
    assert idle_ms_a_step == pytest.approx((103 + 118) / 2 / 3)
    text = "\n".join(said)
    # computed once for the five readers
    assert text.count("device-idle ms a step") == 1
    assert "3 traced `executor_run` spans paired" in text
    assert "spread 0.00 us" in text
    assert "3 `run` roots wholly inside the traced window" in text
    assert "'jit_step_fn(2)' ran {0: 3, 1: 3} times" in text
    # fetch on device 0: 5 of 11 ms in the program; device 1: 0 of 6
    assert "of fetch's 8.5000 ms, 2.5000 lie before" in text
    # dispatch starts at 7; the program at 20 and 30
    assert "mean 18.0000 ms, least 13.0000, most 23.0000 over 6" in text
    assert "10 steps before the profiler started" in text
    assert "dispatch 3.0000, fetch 85.0000, h2d 4.0000, run 96.0000, " \
           "run_self 4.0000" in text
    assert "1000000 bytes a step" in text and "250.0 MB/s" in text


@pytest.mark.parametrize("kwargs, reason", [
    ({"jitter_us": 60.0}, "spread exceeds 50 us"),
    ({"devices": False}, "no device plane"),
    ({"traced_steps": 2}, "2 `executor_run` spans pair"),
    ({"program_spans": False}, "no `run` root span"),
], ids=["clock-spread", "no-device-plane", "too-few-pairs",
        "uninstrumented-program"])
def test_none_with_the_reason_said(kwargs, reason):
    ctx, said = build(**kwargs)
    for part in _phases.PARTS:
        assert _phases.reader(part)(ctx) is None
    assert any(reason in line for line in said), said


def test_none_when_the_ring_dropped_spans():
    ctx, said = build()
    tracer = telemetry.get_tracer()
    for _ in range(tracer.capacity + 1):
        tracer._record("h2d", 0.0, 0.0)
    assert _phases.reader("h2d")(ctx) is None
    assert any("dropped" in line for line in said)


def test_without_a_modules_line_nothing_moves_from_fetch_to_dispatch():
    ctx, _ = build()
    ctx["trace"]["reduced"]["modules"] = {}
    assert _phases.reader("dispatch")(ctx) == pytest.approx(3.0, abs=1e-6)
    assert _phases.reader("fetch")(ctx) == pytest.approx(
        BY_HAND["fetch"] + BY_HAND["dispatch"] - 3.0, abs=1e-6)


def test_says_how_far_the_device_clock_leads_the_hosts():
    """The host issues programs only from inside ``h2d`` and ``dispatch``.
    A cast program that shows on the device between two steps, or while
    the PREVIOUS step's ``fetch`` is still open, measures by its distance
    to the next ``h2d`` how far the device plane's clock runs ahead; the
    hand-built run's own cast (inside ``h2d``) and step programs do not."""
    ctx, said = build()
    _phases.reader("h2d")(ctx)
    assert not [x for x in said if "leads" in x]
    ctx, said = build()
    mods = ctx["trace"]["reduced"]["modules"]
    mods[1] = sorted(mods[1] + [
        (ns(T0 + 0.0995), 900.0, "jit_convert_element_type(1)"),
        (ns(T0 + PERIOD + 0.0955), 900.0, "jit_convert_element_type(1)")])
    _phases.reader("h2d")(ctx)
    [line] = [x for x in said if "leads" in x]
    # 99.5 -> h2d at 102 between two steps; 195.5 (in fetch) -> h2d at 202
    assert "2 device programs" in line and "up to 6.5000 ms" in line


def test_a_program_that_seems_to_begin_before_its_dispatch_is_still_its_own():
    """With a fast launch and the device clock ahead, step k's program
    shows 0.5 ms BEFORE dispatch k opened; it must not be passed over for
    step k+1's (which would hand all of fetch's idle time to dispatch)."""
    ms = 1e6
    steps = [{"run": (k * 100 * ms, (k * 100 + 98) * ms),
              "dispatch": ((k * 100 + 3) * ms, (k * 100 + 5) * ms),
              "fetch": ((k * 100 + 6) * ms, (k * 100 + 97) * ms)}
             for k in range(3)]
    runs = [((k * 100 + 2.5) * ms, (k * 100 + 95) * ms) for k in range(3)]
    assert _phases.programs_of(steps, runs) == runs
    # an execution from before the window is passed over; one that never
    # came leaves its step without
    assert _phases.programs_of(steps, [(-50 * ms, -1 * ms)] + runs) == runs
    assert _phases.programs_of(steps, runs[:2]) == runs[:2] + [None]
    gaps = [(k * 100 * ms + 95 * ms, (k + 1) * 100 * ms + 2.5 * ms)
            for k in range(2)]
    got, _ = _phases.attribute(gaps, steps, _phases.programs_of(steps, runs))
    assert got["dispatch"] == pytest.approx(0.0)
    assert got["fetch"] == pytest.approx(2 * 2 * ms)    # 95 .. 97 twice
