"""chip_smoke.py's legs at tiny size on the CPU: the same functions the
chip run calls, numpy-reference comparison included (the Pallas leg in
interpret mode), and the refusal to run any leg without a TPU."""

import numpy as np
import pytest

import chip_smoke

TINY = dict(n_keys=1000, batch=1 << 10, n_events=1 << 13, seed=3)


@pytest.fixture(scope="module")
def reference():
    return chip_smoke.q5_reference(TINY["n_keys"], TINY["n_events"],
                                   TINY["batch"], TINY["seed"])


@pytest.fixture(scope="module")
def host_leg(reference):
    return chip_smoke.leg_q5_single(
        "q5-tiny-host", capacity=1 << 12, device=False, topk=50,
        reference=reference, **TINY)


def test_reference_counts_every_event_w_times(reference):
    # each event lands in exactly W sliding windows
    total = sum(int(bids.sum()) for bids, _rev in reference.values())
    assert total == chip_smoke.WINDOW_PANES * TINY["n_events"]


def test_device_born_leg_matches_reference(reference):
    report, rows = chip_smoke.leg_q5_single(
        "q5-tiny-device", capacity=1 << 12, device=True, topk=50,
        reference=reference, **TINY)
    assert report["h2d_bytes"] == 0 and report["device_born"]
    assert report["windows"] == len(reference)
    assert report["rows"] == len(rows["auction"]) == 50 * len(reference)


def test_host_born_leg_matches_reference(host_leg, reference):
    report, _rows = host_leg
    assert report["h2d_bytes"] > 0
    assert report["windows"] == len(reference)
    assert all(report[k] == 0 for k in chip_smoke.FALLBACK_COUNTERS)


def test_mesh_leg_equals_single_chip_on_distinct_devices(host_leg,
                                                         reference):
    import jax
    _report, host_rows = host_leg
    report, _rows = chip_smoke.leg_q5_mesh(
        capacity_per_device=1 << 12, topk=50, reference=reference,
        single_chip_rows=host_rows, **TINY)
    assert report["equals_single_chip"]
    assert len(set(report["state_device_ids"])) == len(jax.devices()) > 1


def test_mesh_leg_over_advancing_ids_reclaims_and_keeps_its_capacity():
    """The short in-flight leg (PR 41): thirteen times the ids that are
    in flight at once over tables that hold six times as many under
    their load limit, rows equal to numpy's (asserted by the leg), every
    shard swept at least twice and as large at the end as it began."""
    import jax
    n_dev = len(jax.devices())
    report = chip_smoke.leg_q5_mesh_inflight(
        capacity_per_device=(1 << 13) // n_dev, batch=1 << 11, seed=3,
        topk=50)
    assert report["capacity_per_device"] * n_dev == 1 << 13
    assert report["state_reclaim_sweeps_total"] >= 2
    assert report["windows"] == 24 + chip_smoke.WINDOW_PANES - 1
    assert all(report[k] == 0 for k in chip_smoke.FALLBACK_COUNTERS)


def test_sessions_leg_equals_numpy_and_fires_at_its_cadence():
    """The Q11 leg (PR 43): every session of the stream equals the numpy
    sessionization (asserted by the leg), through `env.execute()` with
    `async_fire`; the hot bidder of each batch is one session of three
    quarters of the batch."""
    report = chip_smoke.leg_q11_sessions(
        n_keys=500, capacity=1 << 12, batch=1 << 9, n_events=1 << 13,
        seed=3, gap_ms=500, span_ms=8000)
    assert report["sessions"] == report["session_fired_total"] > 500
    assert report["session_fires_total"] >= 2
    assert report["session_fire_rounds_total"] \
        >= report["session_fires_total"]
    assert report["capacity"] == 1 << 12
    assert all(report[k] == 0 for k in chip_smoke.FALLBACK_COUNTERS)


def test_the_sessions_reference_cuts_at_the_gap():
    def gen(idx):
        return {"bidder": np.array([1, 1, 1, 2, 1])[idx],
                "ts": np.array([0, 99, 199, 250, 400])[idx]}

    assert chip_smoke.q11_reference(gen, 5, 100) == {
        (1, 0, 199, 2), (1, 199, 299, 1), (2, 250, 350, 1),
        (1, 400, 500, 1)}


def test_check_rows_rejects_a_wrong_answer(host_leg, reference):
    _report, rows = host_leg
    wrong = dict(rows, bids=rows["bids"] + (np.arange(len(rows["bids"]))
                                            == 0))
    with pytest.raises(AssertionError):
        chip_smoke.check_rows(wrong, reference, 50)
    # dropping a key that is strictly above the threshold is caught too
    end = rows["window_end"][0]
    top = np.argmax(np.where(rows["window_end"] == end, rows["bids"], -1))
    keep = np.arange(len(rows["bids"])) != top
    with pytest.raises(AssertionError):
        chip_smoke.check_rows({k: v[keep] for k, v in rows.items()},
                              reference, 50)


def test_pallas_leg_in_interpret_mode():
    report = chip_smoke.leg_pallas_topk(sizes=(1 << 12, 5000), k=100,
                                        interpret=True)
    assert report["interpret"] is True and report["x64"] is True


def test_main_refuses_to_run_without_a_tpu(monkeypatch, capsys):
    def no_leg(*_a, **_kw):
        raise AssertionError("a leg ran on a CPU backend")

    for name in ("leg_q5_single", "leg_q5_mesh", "leg_q5_mesh_inflight",
                 "leg_pallas_topk", "q5_reference"):
        monkeypatch.setattr(chip_smoke, name, no_leg)
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""
