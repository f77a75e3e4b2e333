"""The reduction from a trace to numbers: first on a trace small enough
to work by hand, then on the trace recorded on the chip (PR 26)."""

import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def _tiny():
    # two devices; times in ns.  dev0: a `while` [0,250) that holds op a
    # [0,100), an all-gather-done [100,150) and op b [150,250); idle
    # [250,400); c [400,500)
    dev0 = {"XLA Ops": [("%while.1 = (s32[]) while(%t)", 0, 250, ""),
                        ("%a.1 = f32[4] fusion(%x)", 0, 100, ""),
                        ("%all-gather-done.1 = f32[8] all-gather-done(%s)",
                         100, 50, ""),
                        ("%b.2 = (bf16[2,4,8], f32[2,8,4]) custom-call(s32[2] %g)",
                         150, 100, ""),
                        ("%c = f32[4] fusion(%all-gather-done.1)", 400, 100, "")],
            "XLA Modules": [("jit_step(1)", 0, 250, ""),
                            ("jit_step(1)", 400, 100, "")]}
    dev1 = {"XLA Ops": [("%a.1 = f32[4] fusion(%x)", 0, 500, "")],
            "XLA Modules": [("jit_step(1)", 0, 500, "")]}
    host = {"t1": [("bench:reconcile", 240, 100, ""),
                   ("bench:step", 0, 1000, ""), ("other", 0, 1000, "")]}
    return tr.TraceView({"/device:TPU:0": dev0, "/device:TPU:1": dev1,
                         "/host:CPU": host}, t0_epoch=10.0)


def test_busy_idle_and_programs_by_hand():
    v = _tiny()
    b = v.busy()
    # dev0 busy [0,250) + [400,500) = 350; dev1 500; window 0..1000
    assert b["devices"] == 2
    assert b["busy_s"] == pytest.approx((350 + 500) / 2 / 1e9)
    assert b["window_s"] == pytest.approx(1000 / 1e9)
    assert v.idle_share() == pytest.approx(100 * (1 - 425 / 1000))
    p = v.program_time(r"jit_step")
    assert p["count"] == pytest.approx(1.5)
    assert p["seconds"] == pytest.approx((350 + 500) / 2 / 1e9)
    k = v.op_time(r"= \(bf16\[[\d,]+\], f32\[[\d,]+\]\) custom-call\(s32\[2\] ")
    assert k["count"] == 0.5 and k["seconds"] == pytest.approx(50 / 1e9)
    assert v.op_time(r"nothing")["count"] == 0


def test_collective_time_exposed_by_hand():
    # anchored on the operation's own name: op c only NAMES the
    # all-gather among its operands and must not count
    e = _tiny().exposed_seconds(r"^%all-gather")
    assert e["count"] == 0.5
    assert e["exposed_seconds"] == pytest.approx(50 / 2 / 1e9)


def test_self_time_of_nested_operations():
    st = {n.split(" ")[0]: sf for n, _, _, sf in
          _tiny().self_times("/device:TPU:0")}
    assert st["%while.1"] == 0 and st["%a.1"] == 100 and st["%c"] == 100


def test_breakdown_names_gaps_by_the_innermost_host_annotation():
    bd = _tiny().breakdown("bench:")
    # grouped by signature (op kind + result type), self time, averaged
    ops = dict(bd["device_ops"])
    assert ops["fusion f32[4]"] == pytest.approx((100 + 100 + 500) / 2 / 1e9)
    assert ops["while (s32[])"] == 0
    gaps = dict(bd["idle_gaps"])
    # gap [250,400): midpoint 325 lies in reconcile [240,340), the
    # innermost; gap [500,1000): only bench:step covers it
    assert gaps["reconcile"] == pytest.approx(150 / 1e9)
    assert gaps["step"] == pytest.approx(500 / 1e9)


def test_round_trip(tmp_path):
    v = _tiny()
    path = str(tmp_path / "t.json.gz")
    v.to_json(path)
    w = tr.from_json(path)
    assert w.busy() == v.busy() and w.t0_epoch == 10.0
    names = [e[0] for e in w.host_events()]
    assert "other" not in names and "bench:step" in names


RECORDED = os.path.join(HERE, "data", "train_d12_v5e.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    """A slice of a train-d12 step traced on a TPU v5e (PR 26)."""
    v = tr.from_json(RECORDED)
    assert v.device_planes() == ["/device:TPU:0"]
    b = v.busy()
    assert 0 < b["busy_s"] <= b["window_s"]
    assert 0 <= v.idle_share() < 100
    from benchmark import harness

    readers = harness.load_layer_metrics()
    step = v.program_time(readers["step_ms.train"].PROGRAM)
    assert step["count"] >= 1 and step["seconds"] > 0
    flash = v.op_time(readers["flash_fwd_roofline.train"].KERNEL)
    assert flash["count"] >= 1 and 0 < flash["seconds"] < b["busy_s"]
    bd = v.breakdown()
    assert 1 <= len(bd["device_ops"]) <= 10
