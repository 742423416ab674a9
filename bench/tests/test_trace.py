"""The trace reduction: on synthetic planes, and on a small trace recorded
on the chip (bench/tests/data, made by record_trace.py)."""
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench.lib import trace

DATA = Path(__file__).resolve().parent / "data"
KERNEL = ('%ell_spmv_pallas.1 = f32[1,8,512] custom-call(f32[192,6] %a), '
          'custom_call_target="tpu_custom_call"')
GATHER = "%fusion.21 = f32[1152] fusion(f32[4096] %x), kind=kCustom"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def planes(device_events, host_events):
    return [NS(name="/device:TPU:0",
               lines=[NS(name="XLA Modules", events=[ev("jit_run", 0, 999)]),
                      NS(name="XLA Ops", events=device_events)]),
            NS(name="#Chip0 Host Interface", lines=[]),
            NS(name="/host:CPU", lines=[NS(name="python",
                                           events=host_events)])]


def test_union_length():
    total, merged = trace.union_length([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert total == 3 + 4
    assert merged == [[0, 3], [5, 9]]


def test_split_busy_and_gaps():
    # the device clock may lead the host's: an op that starts before the
    # host window still counts whole
    dev = [ev(GATHER, -10, 60), ev(KERNEL, 50, 20), ev(GATHER, 90, 10),
           ev(GATHER, 150, 50)]
    host = [ev("bench.window", 0, 200), ev("bench.dispatch", 0, 10),
            ev("bench.wait", 70, 20), ev("bench.block", 100, 100),
            ev("unrelated", 0, 500)]
    s = trace.reduce_planes(planes(dev, host))
    assert s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx((80 + 10 + 50) * 1e-9)
    assert s.kernel_s == pytest.approx(20e-9)
    assert s.other_s == pytest.approx(120e-9)
    assert [(g[0], round(g[1] * 1e9)) for g in s.gaps] == [
        ("bench.block", 50), ("bench.wait", 20)]
    b = s.breakdown()
    assert b["device_ops"][0] == [GATHER[:trace.NAME_CHARS],
                                  pytest.approx(120e-9)]
    assert len(b["device_ops"]) == 2


def test_enclosing():
    iv = [(0, 100), (0, 50), (1, 2), (50, 100), (60, 70), (100, 110),
          (110, 110), (120, 130), (120, 130)]
    assert trace.enclosing(iv) == [True, True, False, True, False, False,
                                   False, True, False]


def test_enclosing_ops_add_to_busy_only():
    """A loop op and its body around the ops they run: the leaves are the
    kernel and XLA time and the breakdown; the loop still makes busy."""
    loop = "%while.3 = (f32[512], f32[512]) while((f32[512], f32[512]) %t)"
    dev = [ev(loop, 0, 100), ev("%body", 5, 90), ev(GATHER, 10, 20),
           ev(KERNEL, 30, 40), ev(GATHER, 75, 15)]
    s = trace.reduce_planes(planes(dev, [ev("bench.window", 0, 200)]))
    assert s.busy_s == pytest.approx(100e-9)
    assert s.kernel_s == pytest.approx(40e-9)
    assert s.other_s == pytest.approx(35e-9)
    assert {n for n, _ in s.ops} == {GATHER, KERNEL}


def test_no_window_is_an_error():
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce_planes(planes([ev(KERNEL, 0, 1)], []))


def test_breakdown_keeps_ten():
    dev = [ev(f"%op.{i} = f32[1] add()", 2 * i, 1) for i in range(30)]
    s = trace.reduce_planes(planes(dev, [ev("bench.window", 0, 100)]))
    b = s.breakdown()
    assert len(b["device_ops"]) == len(b["idle_gaps"]) == trace.TOP


def test_recorded_chip_trace():
    """A tiny cell traced on a TPU v5e: the same reduction, the same
    numbers as when it was recorded, and a Mosaic kernel split from the
    XLA ops around it."""
    expected = json.loads((DATA / "tiny_chain.expected.json").read_text())
    s = trace.reduce(str(DATA / "tiny_chain.xplane.pb"))
    assert s.window_s == expected["window_s"]
    assert s.busy_s == expected["busy_s"]
    assert s.kernel_s == expected["kernel_s"] > 0
    assert s.other_s == expected["other_s"] > 0
    assert 0 < s.busy_s <= s.window_s
    assert s.kernel_s + s.other_s == pytest.approx(s.busy_s, rel=0.02)
    assert s.breakdown() == expected["breakdown"]
    assert any(trace.is_mosaic(n) for n, _ in s.ops)
    assert {g[0] for g in s.gaps} <= {"bench.dispatch", "bench.rescale",
                                      "bench.wait", "bench.block", "host"}
    assert expected["result"]["device"]["kind"] == "TPU v5 lite"


def test_recorded_loop_trace():
    """The tiny cell with K chained calls a program, traced on a TPU v5e:
    each program's ``while`` op encloses its calls on the XLA Ops line and
    counts towards busy only; the per-call sums are the leaves'."""
    from jax.profiler import ProfileData
    expected = json.loads((DATA / "tiny_loop.expected.json").read_text())
    path = str(DATA / "tiny_loop.xplane.pb")
    s = trace.reduce(path)
    assert s.window_s == expected["window_s"]
    assert s.busy_s == expected["busy_s"]
    assert s.kernel_s == expected["kernel_s"] > 0
    assert s.other_s == expected["other_s"] > 0
    assert s.breakdown() == expected["breakdown"]

    ops = [ev for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/device:TPU:")
           for line in plane.lines if line.name == trace.OPS_LINE
           for ev in line.events]
    iv = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in ops]
    outer = trace.enclosing(iv)
    loops = [b - a for ev, (a, b), o in zip(ops, iv, outer) if o]
    leaves = [(ev.name, b - a) for ev, (a, b), o in zip(ops, iv, outer)
              if not o]
    calls = expected["calls"]
    assert expected["calls_per_dispatch"] > 1
    assert len(loops) == calls // expected["calls_per_dispatch"]
    assert all(ev.name.startswith("%while") for ev, o in zip(ops, outer) if o)
    kernels = [d for name, d in leaves if trace.is_mosaic(name)]
    assert len(kernels) == calls            # one diagonal kernel a call
    assert s.kernel_s / calls == pytest.approx(sum(kernels) * 1e-9 / calls)
    assert s.kernel_s + s.other_s == pytest.approx(
        sum(d for _, d in leaves) * 1e-9)
    assert s.kernel_s + s.other_s <= s.busy_s <= s.window_s
    # counted as ops of their own, the loops would pass the busy time
    assert s.kernel_s + s.other_s + sum(loops) * 1e-9 > s.busy_s
    assert expected["result"]["device"]["kind"] == "TPU v5 lite"
