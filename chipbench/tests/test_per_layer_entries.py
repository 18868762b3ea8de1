"""``BENCHMARK.json``'s ``per_layer`` after the fold of PR 46: one entry for a
quantity and the end-to-end metric it moves, its ``workloads`` the cells that
report it, a reader found by the quantity and the configuration's family
(``run.reader_path``).  Held here, off the chip:

(a) each cell of ``BENCHMARK.json`` reports one entry a quantity, ``mfu``
    among them, every one with a reader for the cell's family that reads
    None untraced; a cell that brought ``testdata/per_layer/<cell>.json``
    (a new cell brings its own: nothing here names a cell or a family)
    reports exactly the ``quantities`` that file lists;
(b) each of the 128 (cell, metric) pairs the parent commit declared
    (``testdata/per_layer_at_pr45.json``: the name, the metric moved, the
    file the parent's rule found and its hashes, ``code`` that of the file
    it handed its ``read`` on from where it did, ``read_of``) resolves,
    under the name the cell lists the quantity by today, to that file:
    renamed only (the bytes' hash), or the same code under another
    docstring (the hash of the syntax tree without docstrings), or, where
    copies were merged into one file whose shared function asks the program
    for the constant (``flash_roofline.py`` and ``_lib.flash_roofline``),
    to a file whose values (c) pins;
(c) the merged ``flash_roofline`` reads, on traces built by hand and on the
    recorded trace, the digits the parent's copies read there;
(d) a family without a file of its own reads nothing for a quantity that has
    only families' files, and never another family's operations.

Tier-1 collects these through ``test_attention_yardstick.py``, the one file
of this directory that ``tests/`` imports.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import ast
import hashlib
import os
import re

import pytest

from chipbench import run, selfcheck

KIND = "TPU v5 lite"
PER_CELL = os.path.join(run.HERE, "testdata", "per_layer")
#: quantities that have only families' files: their arithmetic is a family's
FAMILY_BOUND = ("mfu", "moe_experts_roofline", "ssd_scan_roofline",
                "gdn_scan_roofline", "kda_scan_roofline")


def bench():
    return run.load_json(run.ROOT, "BENCHMARK.json")


def quantity(name):
    return name.split(".")[0]


def family(cell):
    return run.load_cell(cell)[2]["builder"]


def entries(cell):
    return [m for m in bench()["per_layer"] if cell in m["workloads"]]


def untraced(builder):
    """A reader's ``ctx`` where nothing was traced, counted or timed."""
    shapes = {"flash_dims": (1, 1, 8, 8), "flash_rows": 1, "head_dim": 8,
              "compute_dtype": "bfloat16", "ce_rows": 8}
    ctx = dict.fromkeys(("rec", "spans", "mix", "cell", "peaks", "trace"))
    return dict(ctx, registry={}, say=lambda msg: None,
                config={"builder": builder, "vocab_size": 8},
                program=selfcheck.RecordedProgram(shapes, 8, ()))


# -- (a) what a cell reports --------------------------------------------------

def cells():
    return [w["name"] for w in bench()["workloads"]]


def stated(cell):
    """The quantities the cell's own file says it reports, or None where the
    cell brought none."""
    path = os.path.join(PER_CELL, cell + ".json")
    return (sorted(run.load_json(path)["quantities"])
            if os.path.exists(path) else None)


@pytest.mark.parametrize("cell", cells())
def test_a_cell_reports_exactly_its_quantities_each_with_a_reader(cell):
    b = bench()
    mine = entries(cell)
    got = sorted(quantity(m["name"]) for m in mine)
    assert len(got) == len(set(got)) and "mfu" in got
    assert stated(cell) in (None, got)
    felt = [m["name"] for m in run.metrics_of(b, "end_to_end", cell)
            if m["name"] != "setup_s"]
    builder = family(cell)
    for m in mine:
        assert m["moves"] in felt, m["name"]
        assert m["workloads"] == sorted(m["workloads"], key=cells().index)
        assert run.reader_path(m["name"], builder), m["name"]
        assert run.reader(m["name"])(untraced(builder)) is None, m["name"]


def test_a_cells_file_is_a_cells_and_the_entries_fit():
    """No file of ``testdata/per_layer/`` outlives its cell;
    ``per_layer`` is within the 128 entries the contract allows; the
    entries that ``testdata/per_layer_unfolded.json`` says still wait for a
    pin under ``tests/`` to go are entries, each named there once."""
    names = [m["name"] for m in bench()["per_layer"]]
    assert {f[:-5] for f in os.listdir(PER_CELL)} <= set(cells())
    assert len(names) == len(set(names)) <= 128
    waits = run.load_json(run.HERE, "testdata", "per_layer_unfolded.json")
    listed = [n for group in (*waits["pinned_by"].values(),
                              waits["kept_in_front_of_the_pinned"]["entries"],
                              waits["every_twin_pinned"]["entries"])
              for n in group]
    assert len(listed) == len(set(listed)) and set(listed) <= set(names)


def test_the_entries_of_a_quantity_agree_and_every_file_reads_one():
    """``unit``, ``better``, ``source`` and ``layer`` are a quantity's, not an
    entry's; an entry is told from its twins by the cells it lists; every
    reader file is a declared quantity's, for a family some configuration
    names or for all."""
    b = bench()
    said, listed = {}, set()
    for m in b["per_layer"]:
        what = (m["unit"], m["better"], m["source"], m["layer"])
        assert said.setdefault(quantity(m["name"]), what) == what, m["name"]
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert (quantity(m["name"]), cell) not in listed, m["name"]
            listed.add((quantity(m["name"]), cell))
    families = {run.load_json(run.ROOT, c["file"])["builder"]
                for c in b["configs"]}
    for f in os.listdir(os.path.join(run.HERE, "metrics")):
        if f.endswith(".py") and not f.startswith("_"):
            q, _, fam = f[:-3].partition(".")
            assert q in said or q == "train_tokens_per_s", f
            assert not fam or fam in families, f
    assert not {q for q in said if q.startswith(
        ("data_wait_ms_per_step", "executor_host_ms_per_step"))}


# -- (b) every pair of the parent resolves to the file it had -----------------

ROWS = run.load_json(run.HERE, "testdata", "per_layer_at_pr45.json")
DELEGATE = re.compile(r'^from chipbench\.run import reader\n\nread = reader'
                      r'\("([^"]+)"(?:, "([^"]+)")?\)$', re.M)
#: merged into one file; ``_lib.flash_roofline`` asks the program for the
#: constant: held by (c)
MERGED = {"flash_roofline"}


def sha(data):
    return hashlib.sha256(data).hexdigest()[:16]


def code_sha(path):
    """Hash of a file's syntax tree without its docstrings."""
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        body = getattr(node, "body", None)
        if (isinstance(node, (ast.Module, ast.FunctionDef)) and body
                and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)):
            node.body = body[1:] or [ast.Pass()]
    return sha(ast.dump(tree).encode())


def effective(path):
    """``path``, or the file it hands its ``read`` on from."""
    with open(path) as f:
        m = DELEGATE.search(f.read())
    return effective(run.reader_path(*m.groups())) if m else path


@pytest.mark.parametrize("row", ROWS, ids=lambda r: f"{r['cell']}:{r['name']}")
def test_the_pair_resolves_to_the_file_the_parents_name_found(row):
    assert len(ROWS) == 128
    now = [m for m in entries(row["cell"])
           if quantity(m["name"]) == quantity(row["name"])]
    assert len(now) == 1 and now[0]["moves"] == row["moves"]
    path = run.reader_path(now[0]["name"], family(row["cell"]))
    assert path, now[0]["name"]
    with open(path, "rb") as f:
        if sha(f.read()) == row["sha256"]:
            return                                  # renamed at most
    if code_sha(effective(path)) == row["code"]:
        return                                      # the same code
    assert quantity(row["name"]) in MERGED, (row, path)


# -- (c) the merged flash_roofline reads the parent's digits ------------------

#: ``repr`` of what the parent's files read (commit 5343fbf, before the
#: rename) on ``test_attention_yardstick.synth``'s traces: BERT's shard of 12
#: layers at head 64 on one device and on four, and one layer at head 128
#: over 4,096 positions; the four causal copies read alike
PINNED = [
    # family, causal as its builder states it, trace, parent's name, value
    ("bert", False, "bert", "flash_roofline", 23.47893094260054),
    ("bert", False, "bert x4", "flash_roofline.dp4", 23.47893094260054),
    ("bert", False, "head 128", "flash_roofline", 125.22096502720291),
    ("llama", True, "bert", "flash_roofline.olmoe", 18.909231896308818),
    ("llama", True, "head 128", "flash_roofline.olmoe", 62.610482513601454),
    ("qwen3_next", True, "bert", "flash_roofline.qwen3next",
     18.909231896308818),
    ("qwen3_next", True, "head 128", "flash_roofline.qwen3next",
     62.610482513601454),
    ("nemotron_h", True, "bert", "flash_roofline.nemotronh",
     18.909231896308818),
    ("nemotron_h", True, "head 128", "flash_roofline.nemotronh",
     62.610482513601454),
    ("granite_hybrid", True, "bert", "flash_roofline.granite",
     18.909231896308818),
    ("granite_hybrid", True, "head 128", "flash_roofline.granite",
     62.610482513601454),
]
#: the mask each of those families' files held at the parent; a family that
#: came later is not in it
MASK_AT_PR45 = {row[0]: row[1] for row in PINNED}


@pytest.mark.parametrize("builder, causal, trace, was, value", PINNED,
                         ids=lambda x: str(x).replace(" ", "_"))
def test_flash_roofline_reads_the_parents_digits(builder, causal, trace, was,
                                                 value):
    from chipbench.tests import test_attention_yardstick as ya
    if trace == "head 128":
        shapes = {"flash_dims": (2, 16, 4096, 128), "flash_rows": 32,
                  "flash_elements": 2 * 16 * 4096 * 128, "head_dim": 128,
                  "attention_layers": 1}
        prog = selfcheck.RecordedProgram(
            dict(ya.SHAPES, causal=causal, **shapes), 4096, ya.KERNELS)
        reduced = ya.synth("bf16_32_4096_128", layers=1)
    else:
        prog = ya.program(causal=causal)
        reduced = ya.synth(devices=(0, 1, 2, 3) if "x4" in trace else (0,))
    name = "flash_roofline" + (".dp4" if was.endswith(".dp4") else "")
    assert run.reader_path(name, builder).endswith("flash_roofline.py")
    ctx = dict(selfcheck.trace_ctx(reduced, prog, KIND),
               config={"builder": builder})
    assert run.reader(name)(ctx) == value
    # the recorded trace of BERT's shard: ``selfcheck.check_attention`` holds
    # the merged file to what was read on the chip (``expected.json``)


# -- (d) a family without a file of its own reads nothing ---------------------

@pytest.mark.parametrize("name", FAMILY_BOUND)
def test_a_family_without_a_file_reads_nothing_of_another_familys(name):
    assert run.reader_path(name, "made_up_family") is None
    assert run.reader_path(name) is None
    said = []
    ctx = dict(untraced("made_up_family"), say=said.append)
    assert run.reader(name)(ctx) is None
    assert run.reader(name)(dict(ctx, config=None)) is None
    assert len(said) == 2 and "made_up_family" in said[0]
    # a quantity every family reads alike is read for it too
    assert run.reader_path("device_idle_share", "made_up_family").endswith(
        "device_idle_share.py")
