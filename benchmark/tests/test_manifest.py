"""``BENCHMARK.json`` against the files it names: every metric has its
reader and every reader its entry, every ``workloads`` list names cells,
and a metric several models have finds, in each cell that lists it, the
function it asks the model's own readers for (``program.readers`` of
the cell's configuration, ``readers.for_run``). Imports no JAX.
"""

import ast
import importlib
import re
from pathlib import Path

import pytest

from benchmark import run as bench_run
from benchmark.lib import common, readers

ROOT = Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmark" / "metrics"
BENCH = common.load_json(ROOT / "BENCHMARK.json")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
ENTRIES = BENCH["end_to_end"] + BENCH["per_layer"]
# a metric's suffix says whose it is: one model's own mechanism, or the
# twin of an un-suffixed entry that moves another end-to-end metric
MODELS = ("longcat", "kexaone", "axk1", "jamba", "sdar", "lfm2")


def _cfg(cell: str) -> dict:
    return common.load_json(ROOT / CONFIGS[CELLS[cell]["config"]]["file"])


def _asked_of_the_model(name: str):
    """``(reader, has a fallback)`` where the metric's file asks the
    model's readers (``readers.of_model(run, "<reader>")``), else
    None."""
    src = (METRICS / f"{name}.py").read_text()
    m = re.search(r'of_model\(\s*run,\s*"(\w+)"', src)
    return (m.group(1), "fallback=" in src) if m else None


def _read_body(name: str) -> str:
    tree = ast.parse((METRICS / f"{name}.py").read_text())
    [fn] = [n for n in tree.body
            if isinstance(n, ast.FunctionDef) and n.name == "read"]
    return ast.dump(ast.Module(body=fn.body, type_ignores=[]))


def test_per_layer_is_within_what_the_harness_allows():
    assert 1 <= len(BENCH["per_layer"]) <= 128


def test_every_reader_has_its_entry():
    files = {p.name[:-3] for p in METRICS.glob("*.py")}
    assert files == {m["name"] for m in ENTRIES}
    assert len(ENTRIES) == len(files)       # and no name twice


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda m: m["name"])
def test_entry(entry):
    name = entry["name"]
    assert (METRICS / f"{name}.py").exists()
    per_layer = entry in BENCH["per_layer"]
    if per_layer:
        # an explicit list: a new cell is added by appending its name,
        # and "no list = every cell that reports what it moves" catches
        # nobody
        assert entry.get("workloads"), name
        moved = [m for m in BENCH["end_to_end"]
                 if m["name"] == entry["moves"]]
        assert moved, (name, entry["moves"])
    for cell in entry.get("workloads", ()):
        assert cell in CELLS, (name, cell)
        if per_layer:   # the cell reports what the metric moves
            assert cell in moved[0].get("workloads", CELLS), (name, cell)
    suffix = name.partition(".")[2]
    if suffix in MODELS:    # one model's own: one configuration's cells
        assert len({CELLS[c]["config"] for c in entry["workloads"]}) == 1


@pytest.mark.parametrize("config", sorted(
    c["name"] for c in BENCH["configs"]
    if common.load_json(ROOT / c["file"])["program"]["family"] != "train"))
def test_a_served_configuration_names_its_readers(config):
    cfg = common.load_json(ROOT / CONFIGS[config]["file"])
    name = cfg["program"]["readers"]
    assert (ROOT / "benchmark" / "lib" / f"{name}.py").exists()
    assert readers.for_run(dict(cfg=cfg)) \
        is importlib.import_module(f"benchmark.lib.{name}")


@pytest.mark.parametrize("name,cell", [
    (m["name"], cell) for m in BENCH["per_layer"]
    if _asked_of_the_model(m["name"]) for cell in m["workloads"]])
def test_a_shared_metric_finds_the_models_reader(name, cell):
    reader, fallback = _asked_of_the_model(name)
    assert "." not in name      # no model's or cell's name in the file
    fn = getattr(readers.for_run(dict(cfg=_cfg(cell))), reader, None)
    assert callable(fn) or fallback, (
        f"{name} is listed for {cell}, whose readers "
        f"({_cfg(cell)['program']['readers']}) have no {reader}")


def test_a_model_without_the_reader_says_nothing():
    run = dict(cfg=dict(program=dict(readers="readers")))
    assert readers.of_model(run, "state_bytes_share_pct") is None
    assert readers.of_model(run, "nothing_of_the_kind",
                            fallback=lambda r: 7.0) == 7.0
    # a configuration from before ``program.readers`` gets the generic
    assert readers.for_run(dict(cfg={})) is readers


def test_no_two_entries_read_alike_unless_they_move_apart():
    """Two files with one ``read`` body are one metric written twice,
    unless a metric's one ``moves`` forces the twin (the chat cell
    reports ``itl_p95`` and ``ttft_p90``, the training cells
    ``train_throughput``)."""
    by_body: dict = {}
    for m in BENCH["per_layer"]:
        by_body.setdefault(_read_body(m["name"]), []).append(m)
    for twins in by_body.values():
        moves = [m["moves"] for m in twins]
        assert len(set(moves)) == len(moves), [m["name"] for m in twins]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_reports_what_the_contract_asks(cell):
    e2e = [m["name"] for m in bench_run.metrics_for(BENCH, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert bench_run.metrics_for(BENCH, cell, True)
