"""`stall_share` (PR 54) on made timelines: the program's step ledger as
`JaxTrainer.fit` writes it under `steps`, rank 0's rows `[step, t_enter,
dispatch_s, report_s, flags]`.  The values are worked out by hand."""

import json
import sys

import pytest

from benchmark import harness

CELL = {"cell": {"name": "train-d12"}, "config": {}}
BEGAN = 1_000_000.0
STEP = 0.2              # a quiet step's wall, seconds
PROFILED = 1


def rows_of(walls, flags=None):
    """Ledger rows whose intervals are `walls` (one row more than walls)."""
    t, rows = BEGAN + 30.0, []
    for i in range(len(walls) + 1):
        rows.append([i + 1, t, 0.002, 0.0001, (flags or {}).get(i, 0)])
        t += walls[i] if i < len(walls) else 0.0
    return rows


@pytest.fixture
def put(tmp_path, monkeypatch):
    """Writes a run's timeline.json where the driver puts it, in a process
    that began just before the run's driver did."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS_START",
                        BEGAN - 0.2, raising=False)
    run_dir = tmp_path / "train" / "train-d12"
    run_dir.mkdir(parents=True)

    def write(rows, steps=True):
        doc = {"spans": [{"name": "startup.process", "worker": "driver",
                          "start": BEGAN, "end": BEGAN + 1.0}],
               "compiles": [], "compile_totals": {}}
        if steps:
            doc["steps"] = {"rank0": {"rows": rows, "dropped": 0,
                                      "totals": {"steps": len(rows)}}}
        (run_dir / "timeline.json").write_text(json.dumps(doc))

    return write


def read(counters):
    return harness.load_layer_metrics()["stall_share"].read(
        [], None, counters, CELL)


def window(steps, per_sync=1):
    return {"step_ends": [0.0] * (steps // per_sync),
            "steps_per_sync": per_sync}


def test_a_quiet_window_reads_zero(put):
    # steps that differ by 4 %, as routing moves them: none is a stall
    put(rows_of([STEP * (1.0 + 0.04 * (i % 2)) for i in range(100)]))
    assert read(window(101)) == 0.0


def test_one_interval_of_three_medians_among_a_hundred(put):
    walls = [STEP] * 100
    walls[40] = 3 * STEP
    put(rows_of(walls))
    assert read(window(101)) == pytest.approx(100 * 2 / 102)


def test_only_the_windows_rows_are_read(put):
    """The first step (a compile) and the warm-up stand before the window:
    the window's steps are the LAST rows."""
    put(rows_of([40.0, 3.0, 0.9] + [STEP] * 50))
    assert read(window(51)) == 0.0
    assert read(window(52)) == pytest.approx(100 * 0.7 / (0.9 + 50 * STEP))
    # two steps to a sync: the window's rows are ends x steps_per_sync
    assert read(window(52, per_sync=2)) == pytest.approx(
        100 * 0.7 / (0.9 + 50 * STEP))


def test_profiled_rows_and_their_neighbours_intervals_are_left_out(put):
    """A traced run: the profiler's start lies in the interval BEFORE the
    first profiled row and its stop in the one behind the last."""
    walls = [STEP] * 60
    walls[19] = 2.5          # start_trace, ahead of row 20
    walls[23] = 4.0          # stop_trace, behind row 23
    walls[50] = 2 * STEP     # and one stall of the host's own
    put(rows_of(walls, flags={i: PROFILED for i in (20, 21, 22, 23)}))
    # intervals 19..23 go: 55 are kept, one of them a stall of one median
    assert read(window(61)) == pytest.approx(100 * 1 / 56)


def test_a_program_without_the_ledger_reads_nothing(put, monkeypatch):
    reader = harness.load_layer_metrics()["stall_share"]
    assert reader.read([], None, {}, CELL) is None      # the contract's rule
    assert read(window(101)) is None                    # no file at all
    put([], steps=False)                                # the parent commit's
    assert read(window(101)) is None
    put(rows_of([STEP] * 7))                            # too few to say
    assert read(window(8)) is None
    put(rows_of([STEP] * 100))
    assert read(window(101)) == 0.0
    # an earlier run's file is no reading of this run
    main = sys.modules["__main__"]
    monkeypatch.setattr(main, "T_PROCESS_START", main.T_PROCESS_START + 60)
    assert read(window(101)) is None
