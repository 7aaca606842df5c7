"""A whole run at rehearsal size on the CPU: sound, with the timed path
broken underneath, with the 32-bit control, and the command's refusals.

These skip only the harness's look for a chip (run.py's job); everything
else is the code a chip run drives."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.harness.cell import run_cell
from benchmarks.harness.spec import REPO_ROOT, load_spec

CELL = "q5-10m-saturated"


@pytest.fixture(scope="module")
def spec():
    return load_spec()


def _run(spec, seed=2_147_483_659, cell=CELL, **kw):
    return run_cell(spec, spec.cell(cell), seed=seed, seconds=5.0,
                    trace=False, rehearse=True, **kw)


def _check(run, name):
    return next(c for c in run.checks if c["check"] == name)


def test_sound_run_is_correct_and_counts_add_up(spec):
    run = _run(spec)
    assert run.correct and run.failed == 0
    assert run.attempted == run.timed_events > 0
    assert all(c["ok"] for c in run.checks if "ok" in c)
    tally = _check(run, "_tally")
    assert tally["windows_expected"] == tally["windows_emitted"] > 8
    assert tally["rows_compared"] == 50 * tally["windows_emitted"]
    # set-up ended with every key resident and nothing grown
    assert _check(run, "capacity_grown_by")["value"] == 0
    assert run.builds_in_window == 0
    assert run.t0_s > run.origin_s and run.t_end_s > run.t0_s
    # every batch had exactly batch_rows rows
    assert len(run.reader.emit_s) == run.schedule.n_batches


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        spec, monkeypatch):
    from flink_tpu.runtime.operators.device_window import \
        DeviceWindowAggOperator

    real = DeviceWindowAggOperator._emit_rows
    calls = {"n": 0}

    def off_by_one(self, p_end, keys, results):
        calls["n"] += 1
        if calls["n"] == 7:          # one window, one row, one unit
            results = {k: v.copy() for k, v in results.items()}
            results["revenue"][0] += 1
        return real(self, p_end, keys, results)

    monkeypatch.setattr(DeviceWindowAggOperator, "_emit_rows", off_by_one)
    run = _run(spec)
    assert not run.correct
    assert _check(run, "rows_differ")["value"] == 1


def test_a_part_of_a_batch_left_out_is_not_correct(spec, monkeypatch):
    from flink_tpu.runtime.operators.device_window import \
        DeviceWindowAggOperator

    real = DeviceWindowAggOperator._fold
    calls = {"n": 0}

    def drop_some(self, batch, keys, panes):
        calls["n"] += 1
        if calls["n"] == 30:         # a timed batch loses its last rows
            keep = np.arange(batch.n) < batch.n - 64
            batch, keys, panes = batch.filter(keep), keys[keep], panes[keep]
        return real(self, batch, keys, panes)

    monkeypatch.setattr(DeviceWindowAggOperator, "_fold", drop_some)
    run = _run(spec)
    assert not run.correct
    assert _check(run, "rows_differ")["value"] > 0


def test_a_missing_window_is_not_correct_and_counts_as_failed(
        spec, monkeypatch):
    from flink_tpu.runtime.operators.device_window import \
        DeviceWindowAggOperator

    real = DeviceWindowAggOperator._emit_rows

    def swallow(self, p_end, keys, results):
        if p_end == 12:              # a window of the timed phase
            return None
        return real(self, p_end, keys, results)

    monkeypatch.setattr(DeviceWindowAggOperator, "_emit_rows", swallow)
    run = _run(spec)
    assert not run.correct
    assert _check(run, "windows_missing")["value"] == 1
    assert 0 < run.failed <= run.attempted


def test_sum_kept_in_32_bits_by_the_program_is_not_correct(spec):
    """The control (benchmarks/control.py) at test size: ``price`` declared
    int32 makes the program's own SUM accumulator 32 bits wide."""
    query = spec.module("queries", "q5")
    sum32 = [(n, np.int32 if n == "price" else t)
             for n, t in query.SCHEMA_FIELDS]
    run = _run(spec, schema_fields=sum32)
    assert not run.correct
    tally = _check(run, "_tally")
    assert tally["rows_differ"] > 0.3 * tally["rows_compared"]
    assert _check(run, "windows_missing")["value"] == 0


def _command(*args, env=None):
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args], cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_the_command_refuses_to_measure_without_a_tpu():
    proc = _command("--workload", CELL, "--seed", "1", "--seconds", "5",
                    "--trace", "0")
    assert proc.returncode == 2
    assert proc.stdout.strip() == "" and "Nothing was run" in proc.stderr


def test_rehearsal_prints_no_metric_and_no_result_object():
    proc = _command("--workload", "q5-10m-steady", "--seed", "3000000019",
                    "--seconds", "5", "--trace", "1", "--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    assert lines[-1]["rehearsal"] is True and lines[-1]["correct"] is True
    assert not any("metrics" in x or "breakdown" in x for x in lines)
    assert lines[0]["device"] == {"platform": "cpu", "kind": "cpu",
                                  "count": 1}

