"""The trace reduction: busy and idle time, per-operation self time,
per-program executions, exposed collectives and named gaps."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def profile():
    # window 0..1000 ns; chip 0 busy 100..300 and 500..600, chip 1 busy 100..200
    dev0 = plane("/device:TPU:0", {
        tr.OPS_LINE: [
            ev("while.1", 100, 200),  # a loop whose body is the two ops below
            ev("fusion.2", 100, 50),
            ev("union_flash_attention", 150, 100),
            ev("all-to-all.3", 500, 100),
            ev("fusion.4", 550, 20),  # compute that hides part of the collective
        ],
        tr.MODULES_LINE: [ev("jit_serve_step(1)", 100, 200), ev("jit_serve_step(1)", 500, 100)],
        "Steps": [ev("0", 0, 1000)],
    })
    dev1 = plane("/device:TPU:1", {tr.OPS_LINE: [ev("fusion.2", 100, 100)]})
    host = plane("/host:CPU", {
        "python": [ev(tr.WINDOW, 0, 1000), ev("bench.decode", 300, 150),
                   ev("bench.reference", 700, 300), ev("other", 0, 1000)],
    })
    return NS(planes=[dev0, host, dev1, plane("/host:metadata", {})])


def test_busy_idle_and_window():
    red = tr.reduce_profile(profile())
    assert red.window_s == pytest.approx(1e-6)
    # busy: chip 0 300 ns, chip 1 100 ns -> mean 200 ns
    assert red.busy_s == pytest.approx(200e-9)
    assert red.idle_share == pytest.approx(0.8)
    assert red.chips == 2


def test_self_time_does_not_count_a_loop_body_twice():
    red = tr.reduce_profile(profile())
    assert red.op_s["while.1"] == pytest.approx(50e-9)  # 200 - 50 - 100
    assert red.ops_matching("union_flash") == pytest.approx(100e-9)
    assert red.op_s["fusion.2"] == pytest.approx(150e-9)  # 50 on chip 0, 100 on chip 1
    assert red.modules_matching("serve_step") == (2, pytest.approx(300e-9))


def test_collective_exposed_is_the_part_without_compute():
    red = tr.reduce_profile(profile())
    assert red.collective_s == pytest.approx(50e-9)  # 100 ns on chip 0, 0 on chip 1
    assert red.collective_exposed_s == pytest.approx(40e-9)  # 80 ns of 100 uncovered


def test_gaps_are_named_by_the_host_annotation_around_them():
    red = tr.reduce_profile(profile())
    gaps = dict(red.top_gaps(10))
    assert gaps["bench.reference"] == pytest.approx(400e-9)  # 600..1000
    assert gaps["bench.decode"] == pytest.approx(200e-9)  # 300..500
    assert gaps["host"] == pytest.approx(100e-9)  # 0..100
    assert red.top_gaps(1)[0][0] == "bench.reference"


def test_a_trace_without_device_operations_is_refused():
    p = profile()
    p.planes = [x for x in p.planes if not x.name.startswith("/device")]
    with pytest.raises(ValueError):
        tr.reduce_profile(p)


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 5), (8, 9)], 2, 8) == [(2, 5)]


def test_a_recorded_chip_trace():
    """Three decode steps of a two-layer scan over the flash kernel,
    recorded on a TPU v5e under ``Tracer`` (71 kB)."""
    red = tr.reduce_profile(tr.load(DATA / "decode_step.xplane.pb"))
    assert red.chips == 1
    assert 0 < red.busy_s < red.window_s < 0.02
    n, s = red.modules_matching("serve_step")
    assert n >= 2 and s > 0
    # the Pallas kernel is found by its name, and is the longest operation
    assert red.top_ops(1)[0][0].startswith("union_flash_attention")
    assert 0 < red.ops_matching("union_flash_attention") <= s
    assert red.collective_s == 0.0
    names = {name for name, _ in red.top_gaps(10)}
    assert names <= {"host", "bench.step"}


def test_short_names_of_hlo_ops():
    assert tr.short_name("%fusion.12 = bf16[8]{0} fusion(%a), kind=kLoop") == "fusion.12"
    assert tr.short_name("jit_serve_step(123)") == "jit_serve_step(123)"
