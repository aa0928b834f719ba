"""The trace reduction on recorded events: no chip, no profiler."""

import glob
import json
import os

import pytest

from benchmark import tracereduce as tr

US = 1000.0  # ns


def hand_events():
    return [
        # two device ops overlapping: busy 0..150 us
        ("device:0", "fusion.1", 0 * US, 100 * US),
        ("device:0", "tpu_custom_call:jvp__ bf16[256,512,64] f32[256,512,1]", 50 * US, 100 * US),
        # a gap 150..300 us under a host span, then an all-reduce and a copy
        ("device:0", "all-reduce.3", 300 * US, 50 * US),
        ("device:0", "copy.7", 350 * US, 50 * US),
        ("host", tr.WINDOW_SPAN, 0 * US, 400 * US),
        ("host", "np.asarray(jax.Array)", 140 * US, 170 * US),
        ("host", "outer", 100 * US, 300 * US),
    ]


def test_busy_idle_and_window():
    r = tr.reduce(hand_events())
    assert r["window_s"] == pytest.approx(400e-6)
    assert r["busy_s"] == pytest.approx(250e-6)
    assert r["idle_share"] == pytest.approx(150 / 400)


def test_per_name_durations_and_shares():
    r = tr.reduce(hand_events())
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(100e-6)
    assert ops["all-reduce.3"] == pytest.approx(50e-6)
    assert r["mosaic_s"] == pytest.approx(100e-6)        # flash share 100/250
    assert r["collective_s"] == pytest.approx(50e-6)     # collective share 50/250
    calls = r["mosaic_calls"]["tpu_custom_call:jvp__ bf16[256,512,64] f32[256,512,1]"]
    assert calls == {"calls": 1, "seconds": pytest.approx(100e-6)}


def test_gap_goes_to_the_innermost_host_span():
    r = tr.reduce(hand_events())
    assert r["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert r["idle_gaps"][0][1] == pytest.approx(150e-6)


def test_window_falls_back_to_device_extent_on_another_clock():
    ev = [e for e in hand_events() if e[0] != "host"]
    ev.append(("host", tr.WINDOW_SPAN, 1e12, 400 * US))  # no device op inside
    r = tr.reduce(ev)
    assert r["window_s"] == pytest.approx(400e-6)


def test_busy_is_averaged_over_chips():
    ev = hand_events() + [("device:1", "fusion.1", 0, 50 * US)]
    r = tr.reduce(ev, n_devices=2)
    assert r["devices_seen"] == 2
    assert r["busy_s"] == pytest.approx((250e-6 + 50e-6) / 2)


def test_no_device_event_is_no_reduction():
    assert tr.reduce([("host", "x", 0, 10)]) is None


@pytest.mark.parametrize("name,kind,collective", [
    ("all-reduce.12", "all-reduce", True),
    ("all-reduce-start.3", "all-reduce", True),
    ("all-gather-done.1", "all-gather", True),
    ("%fusion.3 = bf16[2]{0} fusion(...)", "fusion", False),
    ("custom-call:custom-call bf16[1,2,3]", "custom-call", False),
    ("tpu_custom_call:jvp__ bf16[1,2,3]", "tpu_custom_call", False),
])
def test_op_kind(name, kind, collective):
    assert tr.op_kind(name) == kind
    assert tr.is_collective(name) is collective


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "testdata", "*.events.json"))))
def test_recorded_chip_events_reduce(path):
    """A trimmed event list from a real traced run on the chip."""
    events = [tuple(e) for e in json.load(open(path))]
    r = tr.reduce(events)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert 0.0 <= r["idle_share"] < 1.0
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    expect = json.load(open(path.replace(".events.json", ".expect.json")))
    assert r["idle_share"] == pytest.approx(expect["idle_share"], rel=1e-6)
    assert r["mosaic_s"] == pytest.approx(expect["mosaic_s"], rel=1e-6)
    assert r["collective_s"] == pytest.approx(expect["collective_s"], rel=1e-6)


@pytest.mark.parametrize("name,stats,want", [
    ("%jvp__.1", {"long_name": "%jvp__.1 = (bf16[256,512,64]{2,1,0:T(8,128)(2,1)}, "
                  "f32[256,512,1]{2,1,0}) custom-call(%bitcast.7), "
                  "custom_call_target=\"tpu_custom_call\""},
     "tpu_custom_call:jvp__ bf16[256,512,64] f32[256,512,1]"),
    ("%custom-call.7 = f32[1024,1024]{1,0} custom-call(%p), custom_call_target=\"AllocateBuffer\"", {},
     "custom-call:custom-call f32[1024,1024]"),
    ("%all-reduce-start.5 = f32[8]{0} all-reduce-start(f32[8]{0} %x), replica_groups={}", {},
     "all-reduce-start:all-reduce-start f32[8]"),
    ("copy.12", {"hlo_text": "%copy.12 = bf16[36,513,32,20,64]{4,3,2,1,0} copy(%p)"},
     "copy:copy bf16[36,513,32,20,64]"),
    ("all-reduce.3", {"hlo_category": "all-reduce"}, "all-reduce:all-reduce"),
    ("fusion.9", {"flops": 12}, "fusion"),
])
def test_event_name(name, stats, want):
    got = tr.event_name(name, stats)
    assert got == want
    assert tr.is_mosaic(got) is want.startswith("tpu_custom_call")
    assert tr.is_collective(got) is want.startswith("all-reduce")


def test_async_collective_span_counts_as_collective_not_busy():
    ev = hand_events() + [("async:0", "all-reduce-start:all-reduce-start f32[8]", 100 * US, 150 * US)]
    r = tr.reduce(ev)
    assert r["busy_s"] == pytest.approx(250e-6)
    assert r["collective_s"] == pytest.approx(50e-6 + 150e-6)
