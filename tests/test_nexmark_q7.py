"""NEXmark Q7 (highest bid) as the benchmark runs it, at test size on the
CPU: the job of ``benchmarks/queries/q7.py`` through ``run_cell`` from
the REAL ``benchmarks/`` directory against ``q7_reference.py``.

The query is a tumbling int64 MAX over ``price << 20 | bidder`` per
auction with a top-1 a window, a packing map in front of the ``key_by``
and an unpacking map behind the aggregate. This is also the case of the
harness's query seam (benchmarks/tests/test_second_query.py) that runs
under ``tests/``, where tier-1 sees it.
"""

import json
import shutil

import numpy as np
import pytest

from benchmarks.harness.cell import run_cell
from benchmarks.harness.spec import BENCH_DIR, REPO_ROOT, load_module, \
    load_spec

CELL = "q7-10m-saturated"
SEED = 3_000_000_019          # over 2^31, as the driver's are

#: a query module that is Q7 with its reference fed another ``bidder``
WRONG_REFERENCE = '''
from benchmarks.harness.spec import BENCH_DIR, load_module

_q7 = load_module(BENCH_DIR, "queries", "q7")
globals().update({name: getattr(_q7, name) for name in _q7.__all__})


def make_reference(q, data, on_window):
    ref = _q7.make_reference(q, data, on_window)
    feed = ref.feed
    ref.feed = lambda cols, ts: feed(
        {**cols, "bidder": cols["bidder"] ^ 1}, ts)
    return ref
'''

#: the bids generator with one bid in 211 at exactly ``price_max``: every
#: window then holds several auctions that tie on the highest price
TIES_AT_PRICE_MAX = '''
import numpy as np

from benchmarks.harness.spec import BENCH_DIR, load_module

_bids = load_module(BENCH_DIR, "generators", "bids")


class _Ties(_bids.BidGenerator):
    def columns(self, g):
        cols = super().columns(g)
        g = np.asarray(g, np.int64)
        top = (g >= self.prefill_rows) & (g % 211 == 0)
        cols["price"] = np.where(top, self.price_max, cols["price"])
        return cols


def make_generator(data, prefill_rows, seed):
    return _Ties(n_keys=data["n_keys"], hot_keys=data["hot_keys"],
                 hot_share=data["hot_share"], price_max=data["price_max"],
                 n_bidders=data["n_bidders"],
                 layout_seed=data["layout_seed"],
                 prefill_rows=prefill_rows, seed=seed)
'''


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _run(spec, cell=CELL):
    return run_cell(spec, spec.cell(cell), seed=SEED, seconds=6.0,
                    trace=False, rehearse=True)


def _check(run, name):
    return next(c for c in run.checks if c["check"] == name)


def _second_bench(tmp_path, *, query=None, generator=None):
    """A bench_dir that holds the real cell's data files and, beside
    them, one module of the test's own; everything else the harness
    finds in the real directory (harness/spec.load_module)."""
    bench = tmp_path / "benchmarks"
    for d in ("configs", "traffic", "queries", "generators"):
        (bench / d).mkdir(parents=True)
    with open(f"{BENCH_DIR}/configs/nexmark-q7-10m.json") as f:
        config = json.load(f)
    with open(f"{BENCH_DIR}/traffic/bids-saturated.json") as f:
        traffic = json.load(f)
    if query is not None:
        (bench / "queries" / "q7_other.py").write_text(query)
        config["query"]["module"] = "q7_other"
    if generator is not None:
        (bench / "generators" / "bids_ties.py").write_text(generator)
        traffic["generator"] = "bids_ties"
    (bench / "configs" / "nexmark-q7-10m.json").write_text(
        json.dumps(config))
    (bench / "traffic" / "bids-saturated.json").write_text(
        json.dumps(traffic))
    shutil.copy(f"{REPO_ROOT}/BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return load_spec(str(tmp_path / "BENCHMARK.json"), str(bench))


@pytest.fixture(scope="module")
def sound(spec):
    """One sound run, with the program's counters before and after it,
    its stage spans, and when each map operator ran."""
    from flink_tpu.metrics import DEVICE_STATS
    from flink_tpu.metrics.tracing import TRACER, now_ns
    from flink_tpu.runtime.operators.simple import MapOperator

    real, maps = MapOperator.process_batch, []

    def timed(self, batch):
        t = now_ns()
        real(self, batch)
        maps.append((self.name, t, now_ns()))

    TRACER.reset()
    before = DEVICE_STATS.snapshot()
    MapOperator.process_batch = timed
    try:
        run = _run(spec)
        spans = TRACER.retained_spans()
    finally:
        MapOperator.process_batch = real
        TRACER.reset()
    after = DEVICE_STATS.snapshot()
    return run, spans, maps, {k: after[k] - before[k] for k in (
        "fire_selects_total", "fire_select_passes_total",
        "fire_select_sort_total")}


def test_q7_from_the_real_benchmark_directory_equals_its_reference(sound):
    run = sound[0]
    assert run.query.__file__ == f"{BENCH_DIR}/queries/q7.py"
    assert run.correct and run.failed == 0 and run.attempted > 0, [
        c for c in run.checks if not c.get("ok", True)]
    assert all(c["ok"] for c in run.checks if "ok" in c)
    rows = run.sink.rows()
    assert set(rows) == {"auction", "window_start", "window_end", "price",
                         "bidder"}
    tally = _check(run, "_tally")
    # one row a window: prefill 2, warm 2, timed 6 windows at the least
    assert tally["windows_expected"] == tally["windows_emitted"] >= 10
    assert tally["rows_compared"] == tally["windows_emitted"] \
        == len(rows["auction"])
    # the rows unpack a winner: a price of the generator's, a real bidder
    data, q = run.config["data"], run.config["query"]
    assert ((rows["price"] >= 1) & (rows["price"] <= data["price_max"])).all()
    assert ((rows["bidder"] >= 0)
            & (rows["bidder"] < data["n_bidders"])).all()
    assert rows["bidder"].any()
    assert (rows["window_end"] - rows["window_start"]
            == q["window_size_ms"]).all()
    # the job is more than source -> window -> sink: both maps are there
    names = [getattr(op, "name", "") for t in run.job.tasks.values()
             for op in getattr(getattr(t, "chain", None), "operators", ())]
    assert any("PackBid" in n for n in names), names
    assert any("UnpackWinner" in n for n in names), names


def test_the_one_chip_fire_counts_its_select_and_names_it_on_the_drain(
        sound):
    """Every ranked fire is one `fire_selects_total`, its passes the bit
    length of the window's winning word (the select walks the bits the
    data has), none takes a fallback; the same number is the
    `select_passes` attribute of the window's `window/Drain`."""
    run, spans, _maps, grew = sound
    rows, shift = run.sink.rows(), run.config["query"]["word_shift"]
    passes = {int(e): ((int(p) << shift) | int(b)).bit_length()
              for e, p, b in zip(rows["window_end"], rows["price"],
                                 rows["bidder"])}
    assert set(passes.values()) <= {42, 43}
    assert grew["fire_selects_total"] == len(passes)
    assert grew["fire_select_passes_total"] == sum(passes.values())
    assert grew["fire_select_sort_total"] == 0
    drains = [s for s in spans if (s.scope, s.name) == ("window", "Drain")]
    assert {s.attributes["seq"]: s.attributes["select_passes"]
            for s in drains} == passes


def test_the_two_maps_run_inside_the_stage_spans_that_are_there(sound):
    """The packing map runs in the source task's chain, inside
    `task/SourceBatch`; the unpacking map in the window task's chain,
    inside the fired window's `window/Emit`: neither needs a span of its
    own to be attributed."""
    _run_, spans, maps, _grew = sound

    def held_by(name, a, b):
        return any(s.name == name and s.start_ns <= a and b <= s.end_ns
                   for s in spans)

    packs = [(a, b) for n, a, b in maps if n == "PackBid"]
    unpacks = [(a, b) for n, a, b in maps if n == "UnpackWinner"]
    assert packs and unpacks
    assert all(held_by("SourceBatch", a, b) for a, b in packs)
    assert all(held_by("Emit", a, b) for a, b in unpacks)


def test_a_reference_fed_another_bidder_makes_it_not_correct(tmp_path):
    run = _run(_second_bench(tmp_path, query=WRONG_REFERENCE))
    assert run.query.__file__.startswith(str(tmp_path))
    assert not run.correct
    tally = _check(run, "_tally")
    assert tally["windows_missing"] == 0 and tally["bounds_wrong"] == 0
    assert tally["rows_differ"] == tally["rows_compared"] > 0
    # a row that differs is counted once, as a row
    assert tally["topk_wrong"] == 0


def test_a_tie_on_price_goes_to_the_larger_bidder_and_the_word_has_43_bits(
        tmp_path):
    """With bids AT ``price_max`` (the generator draws [1, price_max]
    inclusive) the packed word reaches 2^42 and above: 43 bits, which is
    what ``build`` promises the aggregate; several auctions tie on that
    price in every window and the larger bidder id wins, exactly."""
    run = _run(_second_bench(tmp_path, generator=TIES_AT_PRICE_MAX))
    assert run.correct and run.failed == 0, [
        c for c in run.checks if not c.get("ok", True)]
    q, data = run.config["query"], run.config["data"]
    assert run.query.word_bits(q) == 43
    assert run.operator._aggs[0].value_bits == 43
    assert run.operator._aggs[0].dtype == np.int64
    rows = run.sink.rows()
    timed = run.schedule.phase("timed")
    cols = [run.generator.columns(run.schedule.batch_index(b))
            for b in range(timed.first_batch,
                           timed.first_batch + timed.n_batches)]
    ts = np.concatenate([run.schedule.batch_ts(b) for b in range(
        timed.first_batch, timed.first_batch + timed.n_batches)])
    price = np.concatenate([c["price"] for c in cols])
    bidder = np.concatenate([c["bidder"] for c in cols])
    auction = np.concatenate([c["auction"] for c in cols])
    size = q["window_size_ms"]
    checked = 0
    for end, a, p, b in zip(rows["window_end"], rows["auction"],
                            rows["price"], rows["bidder"]):
        inside = (ts >= end - size) & (ts < end)
        if not inside.any() or ts.min() > end - size:
            continue              # a window the warm phase shares
        at_max = inside & (price == data["price_max"])
        assert len(np.unique(auction[at_max])) > 1     # a real tie
        assert p == data["price_max"] and b == bidder[at_max].max()
        assert ((int(p) << q["word_shift"]) | int(b)).bit_length() == 43
        checked += 1
    assert checked >= 4


@pytest.mark.parametrize("block,key,value,message", [
    ("query", "price_bits", 22, "price_bits"),
    ("data", "price_max", 1 << 30, "price_bits"),
    ("data", "n_bidders", (1 << 20) + 1, "n_bidders"),
])
def test_the_reference_refuses_data_that_break_the_words_promise(
        spec, block, key, value, message):
    q7 = load_module(BENCH_DIR, "queries", "q7")
    config = json.loads(json.dumps(spec.cell(CELL).config))
    q7.make_reference(config["query"], config["data"], lambda *_: None)
    config[block][key] = value
    with pytest.raises(ValueError, match=message):
        q7.make_reference(config["query"], config["data"], lambda *_: None)


def test_the_reference_takes_nothing_from_the_program():
    src = open(f"{BENCH_DIR}/queries/q7_reference.py").read()
    assert "flink_tpu" not in src.split('"""', 2)[2]
    ref = load_module(BENCH_DIR, "queries", "q7_reference")
    seen = []
    r = ref.Q7Reference(8, 1000, 20, lambda end, best: seen.append(
        (end, best.copy())))
    # one batch over two windows; auction 3 ties on price, bidder decides
    r.feed(np.array([3, 3, 5, 3]), np.array([7, 7, 2, 9]),
           np.array([1, 4, 9, 0]), np.array([10, 20, 30, 1500]))
    r.close()
    (e0, b0), (e1, b1) = seen
    assert (e0, e1) == (1000, 2000)
    assert b0[3] == (7 << 20) | 4 and b0[5] == (2 << 20) | 9
    assert b1[3] == 9 << 20 and not b1[5]
    assert r.pane_events == {0: 3, 1: 1}
    ok = ref.check_window(np.array([3]), np.array([7]), np.array([4]),
                          b0, 20)
    assert (ok.rows, ok.rows_differ, ok.topk_wrong) == (1, 0, 0)
    loser = ref.check_window(np.array([5]), np.array([2]), np.array([9]),
                             b0, 20)
    assert (loser.rows_differ, loser.topk_wrong) == (0, 1)
    wrong = ref.check_window(np.array([3]), np.array([7]), np.array([1]),
                             b0, 20)
    assert (wrong.rows_differ, wrong.topk_wrong) == (1, 0)
    two = ref.check_window(np.array([3, 5]), np.array([7, 2]),
                           np.array([4, 9]), b0, 20)
    assert (two.rows_differ, two.topk_wrong) == (0, 1)
