"""The eight readers PR 27 added, on what a run of `train-d12` on the chip
left behind (recorded, PR 27): its `timeline.json` and the host plane's
`ray_tpu:` annotations of its traced window.  The expected values were
worked out from the two files by hand (`RECORDED` says from which rows)."""

import json
import os
import shutil
import sys

import pytest

from benchmark import harness, timeline_lib
from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TIMELINE = os.path.join(DATA, "timeline_train_d12_v5e.json")
HOST_PLANE = os.path.join(DATA, "host_plane_train_d12_v5e.json")
CELL = {"cell": {"name": "train-d12"}}

# reader -> (what it reads in the recorded files, the value by hand)
RECORDED = json.load(open(os.path.join(DATA, "timeline_expected.json")))


@pytest.fixture
def recorded_run(tmp_path, monkeypatch):
    """The recorded timeline where the driver would have put it, in a
    process that began just after the recorded run's did."""
    run_dir = tmp_path / "train" / "train-d12"
    run_dir.mkdir(parents=True)
    shutil.copy(TIMELINE, run_dir / "timeline.json")
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    with open(TIMELINE) as f:
        began = timeline_lib.spans(json.load(f), "startup.process",
                                   "driver")[0]["start"]
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS_START",
                        began + 0.2, raising=False)
    return tr.from_json(HOST_PLANE)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_reader_on_the_recorded_run(name, recorded_run):
    reader = harness.load_layer_metrics()[name]
    value = reader.read([], recorded_run, {}, CELL)
    assert value == pytest.approx(RECORDED[name]["value"], rel=1e-6)


def test_the_recording_covers_every_new_reader():
    new = {n for n, m in harness.load_layer_metrics().items()
           if m.MOVES == "setup_s" or n in ("step_dispatch_ms.train",
                                            "report_ms.train")}
    assert new == set(RECORDED) and len(new) == 8


def test_readers_give_nothing_on_a_program_without_the_spans(
        tmp_path, monkeypatch):
    """The parent commit: no timeline.json, no `ray_tpu:` event in the
    trace, and (rehearsal) no trace at all.  None, and no exception."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS_START", 0.0,
                        raising=False)
    bare = tr.TraceView({"/host:CPU": {"t": [("bench:step_dispatch", 0, 5,
                                              "")]}})
    readers = harness.load_layer_metrics()
    for name in RECORDED:
        assert readers[name].read([], bare, {}, CELL) is None
        assert readers[name].read([], None, {}, CELL) is None


def test_a_stale_or_broken_file_gives_nothing(recorded_run, monkeypatch):
    main = sys.modules["__main__"]
    assert timeline_lib.load(CELL) is not None
    monkeypatch.setattr(main, "T_PROCESS_START", main.T_PROCESS_START + 60)
    assert timeline_lib.load(CELL) is None      # an earlier run's file
    monkeypatch.setattr(main, "T_PROCESS_START", main.T_PROCESS_START - 60)
    path = os.path.join(harness.OUT_DIR, "train", "train-d12",
                        "timeline.json")
    with open(path, "w") as f:
        f.write("{not json")
    assert timeline_lib.load(CELL) is None


def test_to_json_keeps_the_prefix_it_is_given(tmp_path):
    """`to_json(keep_host_prefix=...)`: how the host-plane fixture keeps
    the program's annotations and drops the rest."""
    view = tr.TraceView({"/host:CPU": {"t": [
        ("ray_tpu:train.step", 10.0, 4.0, ""),
        ("bench:step_dispatch", 9.0, 6.0, ""), ("other", 0.0, 1.0, "")]}})
    path = str(tmp_path / "v.json")
    view.to_json(path, keep_host_prefix="ray_tpu:")
    kept = [e[0] for e in tr.from_json(path).host_events()]
    assert kept == ["ray_tpu:train.step"]
    assert timeline_lib.host_median_ms(tr.from_json(path),
                                       "ray_tpu:train.step") == 4.0 / 1e6
