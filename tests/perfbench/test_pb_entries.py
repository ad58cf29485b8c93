"""PR 37's twelve ``per_layer`` entries: the readers of PRs 27, 31, 33 and
35 entered as the issue's table has them, each cell reading the ones it
lists, and every reader silent on a program without its records."""
import pytest

import _structure as st
from _pb import BENCH, CELLS
from perfbench import run as harness
from test_pb_span_metrics import make, read

NAMES = [row[0] for row in st.TWELVE]
SETUP = [row[0] for row in st.TWELVE if row[6] is None]


@pytest.mark.parametrize("row", st.TWELVE, ids=lambda r: r[0])
def test_each_entry_stands_as_the_table_has_it(row):
    name, unit, better, source, layer, moves, cells = row
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == (unit, better, source, layer, moves)
    assert entry.get("workloads") == cells
    assert hasattr(harness.load_reader(name), "read")


def test_the_twelve_follow_unscoped_share_in_the_tables_order():
    st.check_twelve(BENCH, st.DISK)


def test_the_share_of_weighted_tokens_stays_a_reader():
    # it reads the mean of the traffic's noise schedule (55%): no change to
    # the program moves it either way
    assert "loss_weighted_share" not in {m["name"] for m in BENCH["per_layer"]}
    assert hasattr(harness.load_reader("loss_weighted_share"), "read")


@pytest.mark.parametrize("cell", st.DECODERS)
def test_each_decoder_cell_reads_its_own_entries(cell):
    reads = {m["name"] for m in harness.load_cell(cell)["per_layer"]}
    assert reads == st.cell_metrics(BENCH, cell)[1]
    own = {row[0] for row in st.TWELVE if row[6] is None or cell in row[6]}
    assert st.DECODER_BASE | own <= reads
    assert len(own) == {st.LAGUNA: 9, st.LFM2: 10, st.SDAR: 9}[cell]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reads_the_four_setup_entries(cell):
    reads = {m["name"] for m in harness.load_cell(cell)["per_layer"]}
    assert set(SETUP) <= reads


def _first_cell(name):
    row, = [r for r in st.TWELVE if r[0] == name]
    return harness.load_cell((row[6] or CELLS)[0])


@pytest.mark.parametrize("metric", NAMES)
def test_a_program_without_records_reads_nothing_and_raises_nothing(metric):
    """The configuration and traffic of a cell that lists the metric, so
    that nothing is missing but the program's records."""
    cell = _first_cell(metric)
    sizes = {"cfg": cell["cfg"], "traffic": cell["traffic_params"]}
    model = harness.load_module("models", cell["config"])
    # no profiler at all
    bare = make(model=model, **sizes)
    bare["profiler"] = None
    assert read(metric, bare) is None
    # spans and an op map, but a rehearsal's trace (no device plane), no
    # counters and no span of set-up
    ctx = make(spans=[("fit.step", 101, 102)],
               ops={"a": "jit(step)/layer0/MoEFFN/moe0/experts/mul"},
               model=model, **sizes)
    assert read(metric, ctx) is None
