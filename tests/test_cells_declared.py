"""The guard of ``tests/cells.py``: what ``tests/`` holds ``BENCHMARK.json``
to must pass on every table a ``benchmark`` PR is entitled to produce, since
such a PR may edit nothing under ``tests/``.  Three tables, built in memory
from today's file:

``today``     the file as it stands;
``folded``    one ``per_layer`` entry for a quantity and the end-to-end
              metric it moves, its ``workloads`` in the cells' order (the
              fold ``PERF.md`` section 7 describes);
``admitted``  the folded table plus the ten quantities ``PERF.md`` section 3
              names for the program's own records: four that move a cell's
              throughput, six that move ``setup_s``, every cell listed.

``declared`` runs for every cell on all three; every
``tests/test_*_cell.py`` states its family's part as ``table_part(bench)``,
which runs on the two tables that are not today's (on today's its own test
runs it); and no test file reads the table's shape past the helper."""

import copy
import glob
import importlib
import os
import re

import pytest

import cells

HERE = os.path.dirname(os.path.abspath(__file__))
#: (quantity, unit, better, source, layer) of PR 53's records, as ``PERF.md``
#: section 3 names them, by the end-to-end metric they move
ADMITTED = {
    "train_tokens_per_s": [
        ("step_stall_share", "%", "lower", "program_counter", "executor"),
        ("step_excess_share", "%", "lower", "program_span", "executor"),
        ("host_runq_wait_ms_per_step", "ms", "lower", "program_span",
         "executor"),
        ("host_invol_switches_per_step", "switches", "lower",
         "program_span", "executor")],
    "setup_s": [
        (name, "s", "lower", source, "executor")
        for name, source in (
            ("setup_import_s", "program_counter"),
            ("setup_executor_init_s", "program_span"),
            ("setup_xla_trace_s", "program_span"),
            ("setup_xla_lower_s", "program_span"),
            ("setup_xla_compile_or_load_s", "program_span"),
            ("setup_outside_program_s", "program_span"))]}
#: the lines that pin the table's shape: a count, an index, a cell's whole
#: list.  ``tests/test_qwen3_next_cell.py`` holds the contract's rule that
#: four-chip cells are at most a quarter of the cells, which is no pin
PINS = re.compile(
    r'len\(bench\["(per_layer|configs|workloads)"\]\)'
    r'|bench\["(per_layer|configs|workloads)"\]\[-?[0-9]+\]'
    r'|len\(mine\) ==|QUANTITIES\)')
MAY_COUNT = {"cells.py": 1, "test_qwen3_next_cell.py": 1}


def folded(bench):
    """``bench`` with one ``per_layer`` entry for each (quantity, metric
    moved), named by the quantity (and the moved metric's suffix where a
    quantity moves two), listing in the cells' order every cell that an
    entry of the group listed."""
    order = [w["name"] for w in bench["workloads"]]
    groups = {}
    for m in bench["per_layer"]:
        key = (cells.quantity(m["name"]), m["moves"])
        entry = groups.setdefault(key, dict(m, workloads=[]))
        entry["workloads"] += m["workloads"]
    moved = {}
    for what, moves in groups:
        moved.setdefault(what, []).append(moves)
    for (what, moves), entry in groups.items():
        suffix = moves.partition(".")[2]
        entry["name"] = (f"{what}.{suffix}"
                         if suffix and len(moved[what]) > 1 else what)
        entry["workloads"].sort(key=order.index)
    return dict(copy.deepcopy(bench), per_layer=list(groups.values()))


def admitted(bench):
    """``folded(bench)`` plus what it lacks of ``ADMITTED``: an entry for
    each throughput metric a step quantity moves, listing that metric's
    cells; an entry for each set-up quantity, listing every cell."""
    out = folded(bench)
    have = {cells.quantity(m["name"]) for m in out["per_layer"]}
    lists = {m["name"]: m["workloads"] for m in out["end_to_end"]
             if "workloads" in m}
    lists["setup_s"] = [w["name"] for w in out["workloads"]]
    for felt, rows in ADMITTED.items():
        for what, unit, better, source, layer in rows:
            for moves in [m for m in lists if m.startswith(felt)]:
                suffix = moves.partition(".")[2]
                if what not in have:
                    out["per_layer"].append({
                        "name": f"{what}.{suffix}" if suffix else what,
                        "unit": unit, "better": better, "source": source,
                        "layer": layer, "moves": moves,
                        "workloads": list(lists[moves])})
    return out


TABLES = {"today": lambda b: b, "folded": folded, "admitted": admitted}
CELLS = [w["name"] for w in cells.bench()["workloads"]]
FAMILIES = sorted(os.path.basename(p)[:-3]
                  for p in glob.glob(os.path.join(HERE, "test_*_cell.py")))


def test_the_tables_are_what_a_benchmark_pr_may_produce():
    """The fold loses no (quantity, cell, metric moved), lists none twice
    and leaves today's file as it was; after the admission every cell lists
    every quantity of ``ADMITTED`` once, and the table fits the contract."""
    today = cells.bench()

    def pairs(bench):
        return sorted((cells.quantity(m["name"]), cell, m["moves"])
                      for m in bench["per_layer"] for cell in m["workloads"])
    fold, more = folded(today), admitted(today)
    assert pairs(fold) == pairs(today) and today == cells.bench()
    names = [m["name"] for m in fold["per_layer"]]
    assert len(names) == len(set(names)) == len(
        {(cells.quantity(m["name"]), m["moves"]) for m in today["per_layer"]})
    listed = [(what, cell) for what, cell, _ in pairs(more)]
    for rows in ADMITTED.values():
        for what, *_ in rows:
            assert [listed.count((what, cell)) for cell in CELLS] == [1] * len(
                CELLS), what
    names = [m["name"] for m in more["per_layer"]]
    assert len(names) == len(set(names)) <= cells.MOST_ENTRIES


@pytest.mark.parametrize("table", TABLES)
@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_is_declared_on_every_table(cell, table):
    cells.declared(TABLES[table](cells.bench()), cell)


@pytest.mark.parametrize("table", ["folded", "admitted"])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_familys_part_holds_on_a_table_it_did_not_see(family, table):
    """``table_part(bench)`` is all a cell's test says of the table, so it
    is all that has to pass where the table is folded or holds more."""
    importlib.import_module(family).table_part(TABLES[table](cells.bench()))


def test_no_test_pins_the_tables_shape():
    """A count of entries, configurations or cells, an index into them, the
    length of a cell's list or the whole list as a literal is read nowhere
    under ``tests/`` but in the helper."""
    found = {}
    for path in glob.glob(os.path.join(HERE, "*.py")):
        with open(path) as f:
            hits = [ln for ln in f if PINS.search(ln)]
        if hits:
            found[os.path.basename(path)] = len(hits)
    assert found == MAY_COUNT, found
