"""``chipbench/metrics/_blocks.py`` off the chip: the by-block account of the
step program's device time, and the five metrics that read it.

As ``chipbench/tests/test_nemotronh_readers.py`` does, a cell's program is built
at toy widths by the cell's builder (the two hybrids with their own layer
patterns), its train step compiled, and a device trace synthesised from the
compiled step's own ENTRY instructions: one event an instruction with a time
of its own, every control-flow instruction filled with events of its bodies
under a key that ENTRY has too.  Three instructions are written into the
text by hand: a collective under a block's ``op_name``, a copy without
metadata between two blocks, and its user.  What the table says is compared
with sums taken by hand, and with what the readers that were there read."""

import importlib
import re
from types import SimpleNamespace

import pytest

import hetu_tpu as ht
from chipbench import run, selfcheck
from chipbench import trace_reduce as tr
from chipbench.metrics import _blocks, _moe, _scopes
from chipbench.tests.test_nemotronh_readers import registry

KIND = "TPU v5 lite"
STEPS, STEP_NS, WARM = 2, 200e6, 3
SSM = ("hetu_ssm_proj", "hetu_ssm_conv", "hetu_ssm_scan", "hetu_ssm_out")
GDN = ("hetu_gdn_proj", "hetu_gdn_conv", "hetu_gdn_scan", "hetu_gdn_out")
MOE = _moe.SCOPES + ("hetu_moe_shared",)
CELLS = {
    "olmoe": ("olmoe-1b-7b.b2-s4096", {}, None),
    "qwen3next": ("qwen3-next-80b-a3b.b1-s8192",
                  {"num_hidden_layers": 4, "full_attention_interval": 4},
                  256),
    "nemotronh": ("nemotron-3-nano-30b-a3b.b1-s8192",
                  {"num_hidden_layers": 9,
                   "hybrid_override_pattern": "MEMEM*EME"}, 256),
}
#: the readers that were there, the scopes each sums, and where it reads
ACCEPTED = {
    "olmoe": [("moe_block_device_ms_per_step", _moe.SCOPES)],
    "qwen3next": [("gdn_block_device_ms_per_step", GDN),
                  ("moe_block_device_ms_per_step.qwen3next", MOE)],
    "nemotronh": [("ssm_block_device_ms_per_step", SSM),
                  ("moe_block_device_ms_per_step.nemotronh", MOE)],
}
#: ``op_name``s that carry no block at toy size: the sums of gradients that
#: autodiff adds (``add_any``), what a recomputed group keeps (``remat2``),
#: the executor's step counter (``add``), and XLA's copies of a parameter,
#: which keep the parameter's
NO_BLOCK = (r"/add_any$", r"/remat2$", r"^jit\(step_fn\)/add$",
            r"^params\[")
NO_METADATA = "copy.99001"


def doctored(text, names):
    """The compiled step's text with three instructions written in before
    ENTRY's root: an all-reduce whose ``op_name`` holds a block, a copy
    without metadata of a result of the first ``hetu_norm`` instruction, and
    a ``hetu_optim`` fusion that uses the copy."""
    insts = _blocks.entry_instructions(text, names)
    made = next(i["name"] for i in insts if i["row"] == "hetu_norm"
                and i["opcode"] == "fusion")
    lines = [
        '  %all-reduce.99000 = f32[8]{0} all-reduce(%' + made + '), '
        'replica_groups={}, metadata={op_name="jit(step_fn)/'
        'transpose(jvp(hetu_attn))/psum"}',
        f'  %{NO_METADATA} = f32[8,3]{{1,0}} copy(%{made})',
        f'  %fusion.99002 = f32[8,3]{{1,0}} fusion(%{NO_METADATA}), '
        'kind=kLoop, calls=%nothing, metadata={op_name="jit(step_fn)/'
        'hetu_optim/mul"}']
    head, root, tail = text.rpartition("  ROOT ")
    assert head.count("\nENTRY ") == 1 and tail.count("\n}") == 1
    return head + "\n".join(lines) + "\n" + root + tail


def synth(insts):
    """``(reduced trace, {row: ms a step by hand}, loops)``: ``STEPS``
    executions of the step; the j-th instruction that runs something takes
    ``1000 + 10 j`` ns.  A control-flow event is filled with three events
    of a body under the key of an instruction that is not control flow, and
    one nested loop; the rows are taken by a rule written out again here."""
    names = set(ht.scopes())
    runs = [i for i in insts if i["opcode"] not in _moe.NO_EVENT
            and not i["key"].startswith(_moe.NO_EVENT)]
    stolen = next(i["key"] for i in runs if i["row"] == "hetu_optim")
    want, events, modules, host, loops = {}, [], [], [], 0
    for step in range(STEPS):
        t0 = 1e9 + step * STEP_NS
        host.append((t0, STEP_NS - 2e3, "executor_run"))
        at = t0 + 1e3
        for j, inst in enumerate(runs):
            ns = 1000.0 + 10 * j
            events.append((at, ns, inst["key"]))
            if inst["key"].startswith(tr.CONTAINERS):
                loops += step == 0
                inner = ns / 8
                for i in range(3):
                    events.append((at + (2 * i + 1) * inner, inner / 2,
                                   stolen))
                events.append((at + 7 * inner, inner / 2, "while_f32_1"))
                events.append((at + 7.1 * inner, inner / 4, stolen))
            found = [n for n in re.findall(r"hetu_[a-z0-9_]+",
                                           inst["op_name"] or "")
                     if n in names]
            row = ("collectives" if inst["opcode"].startswith("all-reduce")
                   else "no_op_name" if inst["op_name"] is None
                   else found[-1] if found else "unscoped")
            want[row] = want.get(row, 0.0) + ns * 1e-6 / STEPS
            at += ns + 50.0
        assert at < t0 + STEP_NS - 3e3
        modules.append((t0 + 500.0, at - t0, "jit_step_fn"))
        modules.append((t0 + 100.0, 300.0, "jit_convert_element_type"))
        events.append((t0 + 100.0, 300.0, "convert_bf16_8"))
    events.sort(key=lambda e: e[0])
    return ({"devices": {0: events}, "modules": {0: modules}, "host": host},
            want, loops)


@pytest.fixture(scope="module", params=sorted(CELLS))
def traced(request):
    cell, over, seq = CELLS[request.param]
    _, _, config, mix = run.load_cell(cell)
    config = run.merge(run.merge(config, config["toy"]), over)
    mix = run.merge(mix, mix["toy"])
    if seq:
        mix["seq"] = seq
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    prog = builder.build(config, mix, 2 ** 31 + 7, lambda msg: None)
    names = ht.scopes()
    text = doctored(prog.ex.subexecutor["train"].lower_compiled().as_text(),
                    names)
    fake = SimpleNamespace(ex=SimpleNamespace(subexecutor={
        "train": SimpleNamespace(lower_compiled=lambda: SimpleNamespace(
            as_text=lambda: text))}))
    insts = _blocks.entry_instructions(text, names)
    reduced, want, loops = synth(insts)
    said = []
    ctx = selfcheck.trace_ctx(reduced, fake, KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=dict(mix, warm_steps=WARM),
               cell={"chips": 1}, registry=registry(WARM + len(ends)),
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    table = _blocks.compute(ctx)
    yield SimpleNamespace(family=request.param, ctx=ctx, insts=insts,
                          want=want, loops=loops, said=said, table=table,
                          reduced=reduced)
    prog.close()


def test_the_rows_add_up_to_every_event_with_loops_taken_whole(traced):
    """Every top-level event of the step program's executions is in one row;
    the events inside a loop are their loop's."""
    by_hand = sum(traced.want.values())
    assert sum(traced.table.values()) == pytest.approx(by_hand, rel=1e-9)
    assert set(traced.table) == set(ht.scopes()) | set(_blocks.OTHER_ROWS)
    for row, ms in traced.table.items():
        assert ms == pytest.approx(traced.want.get(row, 0.0), rel=1e-9,
                                   abs=1e-12), row
    assert not any("split by counts" in line for line in traced.said)
    line = next(s for s in traced.said if "sum" in s and "union" in s)
    assert f"{by_hand:10.3f} ms" in line
    assert "jit_convert_element_type 0.000 ms a step" in line


def test_a_loop_under_a_scope_is_taken_whole(traced):
    if traced.family == "olmoe":
        pytest.skip("the dropless layer over every expert has no loop")
    scan = "hetu_ssm_scan" if traced.family == "nemotronh" else "hetu_gdn_scan"
    under = [i for i in traced.insts if i["row"] == scan
             and i["opcode"] in tr.CONTAINERS]
    assert under and traced.loops >= len(under)
    assert traced.table[scan] == pytest.approx(traced.want[scan], rel=1e-9)


def test_unscoped_is_what_a_hand_sum_says_and_the_rest_carries_a_block(traced):
    """At toy size every ENTRY instruction that has an ``op_name`` carries a
    block but for the few kinds of ``NO_BLOCK``."""
    bare = [i for i in traced.insts if i["row"] == "unscoped"
            and i["opcode"] != "parameter"]
    assert 0 < len(bare) <= 16, [i["op_name"] for i in bare]
    for inst in bare:
        assert any(re.search(p, inst["op_name"]) for p in NO_BLOCK), inst
    assert traced.table["unscoped"] == pytest.approx(
        traced.want["unscoped"], rel=1e-9)
    assert traced.table["unscoped"] < 0.02 * sum(traced.table.values())
    said = [s for s in traced.said if s.startswith("blocks: unscoped:")]
    assert said and all("op_name=" in s for s in said)


def test_a_collective_lands_in_collectives_whatever_its_op_name(traced):
    inst = next(i for i in traced.insts if i["name"] == "all-reduce.99000")
    assert "hetu_attn" in inst["op_name"] and inst["row"] == "collectives"
    assert traced.table["collectives"] == pytest.approx(
        traced.want["collectives"], rel=1e-9)
    assert traced.want["collectives"] > 0


def test_an_instruction_without_metadata_says_its_neighbours_blocks(traced):
    at = next(n for n, i in enumerate(traced.insts)
              if i["name"] == NO_METADATA)
    assert traced.insts[at]["row"] == "no_op_name"
    made, used = _blocks.neighbours(traced.insts)(at)
    assert made.startswith("hetu_norm (") and used.startswith("hetu_optim (")
    assert traced.table["no_op_name"] == pytest.approx(
        traced.want["no_op_name"], rel=1e-9)
    said = [s for s in traced.said if s.startswith("blocks: no_op_name:")]
    assert 0 < len(said) <= _blocks.TOP
    assert all("operand from" in s and "first user" in s for s in said)
    # a copy of a parameter says whose: the parameter's own op_name
    first = next(n for n, i in enumerate(traced.insts) if i["operands"]
                 and i["opcode"] not in _moe.NO_EVENT
                 and any(p["name"] == i["operands"][0]
                         and p["opcode"] == "parameter"
                         for p in traced.insts[:n]))
    assert _blocks.neighbours(traced.insts)(first)[0].startswith("parameter ")


def test_the_readers_that_were_there_read_what_they_read(traced):
    """The table's rows for the scopes an accepted metric sums agree with
    that metric, and both with the hand sum."""
    for name, scopes in ACCEPTED[traced.family]:
        by_hand = sum(traced.want.get(s, 0.0) for s in scopes)
        assert by_hand > 0, name
        assert run.reader(name)(traced.ctx) == pytest.approx(by_hand,
                                                             rel=1e-9), name
        assert sum(traced.table[s] for s in scopes) == pytest.approx(
            by_hand, rel=1e-9), name
    if traced.family != "olmoe":
        scopes = ACCEPTED[traced.family][0][1]
        got = _scopes.scoped_ms(traced.ctx, scopes, "check")
        assert got == pytest.approx({s: traced.table[s] for s in scopes},
                                    rel=1e-9)


def test_the_five_metrics_read_their_rows(traced):
    t, ctx = traced.table, dict(traced.ctx)
    ctx.pop("blocks", None)
    assert run.reader("optim_device_ms_per_step")(ctx) == pytest.approx(
        t["hetu_optim"] + t["hetu_param_cast"])
    assert "blocks" in ctx          # the table is computed once a run
    assert run.reader("attn_block_device_ms_per_step.olmoe")(
        ctx) == pytest.approx(t["hetu_attn"])
    assert run.reader("head_loss_device_ms_per_step.nemotronh")(
        ctx) == pytest.approx(t["hetu_embed"] + t["hetu_head"]
                              + t["hetu_loss"])
    assert run.reader("step_unscoped_device_share.qwen3next")(
        ctx) == pytest.approx(100.0 * (t["unscoped"] + t["no_op_name"])
                              / sum(t.values()))
    assert t["hetu_optim"] > 0 and t["hetu_attn"] > 0 and t["hetu_head"] > 0
    mlp = run.reader("mlp_block_device_ms_per_step")(ctx)
    assert mlp == (0.0 if "hetu_mlp" in t else None)


def test_a_program_without_scopes_reads_none_and_says_so(traced, monkeypatch):
    """A parent commit's program: no ``ht.scopes``.  None, said, not
    raised; and nothing without a whole execution in the window."""
    said = []
    ctx = dict(traced.ctx, say=said.append)
    ctx.pop("blocks", None)
    monkeypatch.delattr(ht, "scopes")
    assert run.reader("optim_device_ms_per_step")(ctx) is None
    assert run.reader("step_unscoped_device_share")(ctx) is None
    assert any("ht.scopes" in line for line in said), said
    monkeypatch.undo()
    cut = dict(traced.reduced, modules={0: []})
    ctx = dict(traced.ctx, say=said.append,
               trace=dict(traced.ctx["trace"], reduced=cut))
    ctx.pop("blocks", None)
    assert run.reader("attn_block_device_ms_per_step")(ctx) is None
    assert any("no whole execution" in line for line in said), said


# -- (d) the declarations --------------------------------------------------------

SUFFIXES = {"": ("bert-base.b64-s512", "train_tokens_per_s"),
            ".dp4": ("bert-base.dp4-b256-s512", "train_tokens_per_s.dp4"),
            ".olmoe": ("olmoe-1b-7b.b2-s4096", "train_tokens_per_s"),
            ".qwen3next": ("qwen3-next-80b-a3b.b1-s8192",
                           "train_tokens_per_s"),
            ".nemotronh": ("nemotron-3-nano-30b-a3b.b1-s8192",
                           "train_tokens_per_s")}
METRICS = {"step_unscoped_device_share": ("%", "model step", SUFFIXES),
           "optim_device_ms_per_step": ("ms", "model step", SUFFIXES),
           "attn_block_device_ms_per_step": ("ms", "kernels", SUFFIXES),
           "head_loss_device_ms_per_step": ("ms", "model step", SUFFIXES),
           "mlp_block_device_ms_per_step": ("ms", "kernels", ("", ".dp4"))}


@pytest.mark.parametrize("name", [base + suffix
                                  for base, (_, _, suffixes) in METRICS.items()
                                  for suffix in suffixes])
def test_the_metric_is_declared_with_a_reader_and_reads_none_untraced(name):
    base, _, suffix = name.partition(".")
    suffix = "." + suffix if suffix else ""
    unit, layer, _ = METRICS[base]
    cell, moves = SUFFIXES[suffix]
    # keyed by QUANTITY and cell, not by the entry's name or place: some
    # entry of the quantity lists the cell, with this unit, layer and metric
    # moved, on today's file and on one whose per-cell entries are folded
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    entry, = (m for m in bench["per_layer"]
              if m["name"].split(".")[0] == base and cell in m["workloads"])
    assert {k: entry[k] for k in entry if k not in ("name", "workloads")} == {
        "unit": unit, "better": "lower", "source": "device_trace",
        "layer": layer, "moves": moves}
    assert run.reader(entry["name"])(
        {"trace": None, "say": lambda msg: None}) is None
    assert run.reader(name)({"trace": None, "say": lambda msg: None}) is None
