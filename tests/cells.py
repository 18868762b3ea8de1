"""What ``tests/`` holds a cell's declaration in ``BENCHMARK.json`` to, said
once.  A cell's test calls ``declared(bench, CELL, own=...)`` and states
nothing else about the table; ``tests/test_cells_declared.py`` runs the same
call for every cell on the tables a ``benchmark`` PR is entitled to produce
(``per_layer`` folded to one entry a quantity, further quantities admitted
to every cell), so a line that pins the table's shape fails at the PR that
writes it.

What is NOT held here, because it is the benchmark's to change: how many
entries, configurations or cells there are and in which order, which entry
is last, how many quantities a cell reports and which (that list is
``chipbench/testdata/per_layer/<cell>.json``'s, held in tier-1 by
``chipbench/tests/test_per_layer_entries.py``), an entry's name beyond its
quantity, and how many cells an entry lists."""

from chipbench import run, traffic

#: the contract's most ``per_layer`` entries
MOST_ENTRIES = 128
#: the contract's most characters of a ``why``
MOST_WHY = 200


def bench():
    """``BENCHMARK.json`` as it stands."""
    return run.load_json(run.ROOT, "BENCHMARK.json")


def quantity(name):
    """What an entry measures: its name before the first dot (what follows
    tells entries of one quantity apart and finds no reader)."""
    return name.split(".")[0]


def declared(bench, cell, own=()):
    """Hold the table ``bench`` to what a cell's declaration owes and return
    the cell's ``per_layer`` entries by quantity.

    The cell is one of ``workloads``, on a configuration ``configs`` names
    and a traffic file that exists; the ``configs`` entry's ``reduced`` and
    ``source`` are the configuration file's; both ``why`` fit; one
    end-to-end throughput metric lists the cell; ``per_layer`` is within
    the contract; exactly one entry of each quantity in ``own`` (the
    family's own mechanism) lists the cell."""
    work, = (w for w in bench["workloads"] if w["name"] == cell)
    entry, = (c for c in bench["configs"] if c["name"] == work["config"])
    config = run.load_json(run.ROOT, entry["file"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"]), cell
    assert entry["source"] == config["source"], cell
    assert traffic.load(work["traffic"]), cell
    assert max(len(work["why"]), len(entry["why"])) <= MOST_WHY, cell
    felt = [m["name"] for m in run.metrics_of(bench, "end_to_end", cell)
            if m["unit"].endswith("/s")]
    assert len(felt) == 1, (cell, felt)
    assert len(bench["per_layer"]) <= MOST_ENTRIES
    mine = [m for m in bench["per_layer"] if cell in m["workloads"]]
    for name in own:
        found = [m["name"] for m in mine if quantity(m["name"]) == name]
        assert len(found) == 1, (cell, name, found)
    return {quantity(m["name"]): m for m in mine}
