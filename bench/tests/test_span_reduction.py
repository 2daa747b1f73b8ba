"""The reduction of the program's own spans (``bench/spans.py``): self
time and counts per span, gaps named by the innermost span of either
kind, idle time split by span, and the host times per query and step."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import spans as sp
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def map_profile():
    # window 0..1000 ns; the chip busy 100..200 and 600..700; one query of
    # the mapping driver with the program's spans nested inside it
    dev = plane("/device:TPU:0", {tr.OPS_LINE: [ev("fusion.1", 100, 100), ev("fusion.2", 600, 100)]})
    host = plane("/host:CPU", {"python": [
        ev(tr.WINDOW, 0, 1000), ev("bench.query.q", 0, 1000), ev("union.map.query", 20, 960),
        ev("union.map.sync", 150, 150), ev("union.map.dedup", 300, 200),
        ev("union.map.offer", 700, 100), ev("union.map.dedup", 820, 80),
        ev("PjitFunction(f)", 150, 10),  # a runtime event: not a span
    ]})
    return NS(planes=[dev, host])


def serve_profile():
    dev = plane("/device:TPU:0", {tr.OPS_LINE: [ev("fusion.1", 200, 100)]})
    host = plane("/host:CPU", {"python": [
        ev(tr.WINDOW, 0, 1000), ev("union.serve.wave", 0, 1000),
        ev("union.serve.init_cache", 10, 40), ev("union.serve.prefill", 100, 100),
        ev("union.serve.dispatch", 100, 100), ev("union.serve.decode", 200, 700),
        ev("union.serve.step", 200, 150), ev("union.serve.dispatch", 200, 50),
        ev("union.serve.pull", 250, 50), ev("union.serve.emit", 300, 50),
    ]})
    return NS(planes=[dev, host])


def test_span_self_times_and_counts():
    red = sp.reduce_spans(map_profile())
    assert red.span_n == {"union.map.query": 1, "union.map.sync": 1,
                          "union.map.dedup": 2, "union.map.offer": 1}
    assert red.span_s["union.map.query"] == pytest.approx(430e-9)  # 960 - 150 - 280 - 100
    assert red.span_s["union.map.sync"] == pytest.approx(150e-9)
    assert red.span_s["union.map.dedup"] == pytest.approx(280e-9)
    assert red.span_s["union.map.offer"] == pytest.approx(100e-9)


def test_gaps_are_named_by_the_innermost_span_of_either_kind():
    red = sp.reduce_spans(map_profile())
    assert red.idle_s == pytest.approx(800e-9)
    assert red.gaps == [("union.map.dedup", pytest.approx(400e-9)),  # 200..600, midpoint in dedup
                        ("union.map.dedup", pytest.approx(300e-9)),  # 700..1000, midpoint in dedup
                        ("union.map.query", pytest.approx(100e-9))]  # 0..100, midpoint in the query


def test_idle_time_is_split_by_the_innermost_span_over_each_part():
    red = sp.reduce_spans(map_profile())
    by = red.idle_by_span
    assert by["bench.query.q"] == pytest.approx(40e-9)  # 0..20 and 980..1000
    assert by["union.map.query"] == pytest.approx(280e-9)  # 20..100, 500..600, 800..820, 900..980
    assert by["union.map.sync"] == pytest.approx(100e-9)  # 200..300
    assert by["union.map.dedup"] == pytest.approx(280e-9)  # 300..500, 820..900
    assert by["union.map.offer"] == pytest.approx(100e-9)  # 700..800
    assert sum(by.values()) == pytest.approx(red.idle_s)
    assert red.idle_share_in("union.map.", exclude=("union.map.query",)) == pytest.approx(0.6)


def test_innermost_segments_cover_the_window():
    host = [tr.Event("a", 10, 50), tr.Event("b", 20, 30), tr.Event("c", 60, 200)]
    assert sp.innermost(host, 0, 100) == [
        (0, 10, "host"), (10, 20, "a"), (20, 30, "b"), (30, 50, "a"), (50, 60, "host"), (60, 100, "c"),
    ]


def test_host_times_per_query_and_per_step():
    m = sp.span_metrics(sp.reduce_spans(map_profile()))
    assert m == {"map.mapper_ms_per_query": pytest.approx(100e-6),
                 "map.engine_host_ms_per_query": pytest.approx(280e-6),
                 "map.sync_ms_per_query": pytest.approx(150e-6)}  # no setup span here
    s = sp.span_metrics(sp.reduce_spans(serve_profile()))
    # (dispatch 150 + emit 50) ns over two steps; the wave's 1000 ns less
    # 100..350 inside the steps' spans
    assert s == {"serve.host_ms_per_step": pytest.approx(100e-6),
                 "serve.wave_edge_ms": pytest.approx(750e-6)}


def test_a_trace_without_spans_gives_no_host_times_and_the_same_gaps():
    """The committed decode-step trace holds no program spans: no number
    is read from it, and its gaps are those ``bench/trace.py`` finds."""
    profile = tr.load(DATA / "decode_step.xplane.pb")
    red, ref = sp.reduce_spans(profile), tr.reduce_profile(profile)
    assert sp.span_metrics(red) == {}
    assert red.span_n == {}
    assert red.gaps == ref.gaps
    assert red.idle_s == pytest.approx(ref.window_s - ref.busy_s, rel=1e-9)
    assert set(red.idle_by_span) <= {"host", "bench.step"}


def test_a_recorded_chip_trace_names_its_gaps_by_the_programs_spans():
    """One wave of a two-layer qwen3-0.6b ``WaveServer`` (one prefill and
    two decode steps) and one ``union_opt`` query on the jax engine,
    recorded on a TPU v5e under ``Tracer`` and cut to 98 kB by
    ``record_trace.slim``."""
    profile = tr.load(DATA / "spans.xplane.pb")
    red = sp.reduce_spans(profile)
    assert red.span_n["union.serve.dispatch"] == 3  # one a step
    assert red.span_n["union.serve.step"] == 2  # the decode steps
    assert red.span_n["union.map.query"] == 1
    assert red.span_n["union.map.sync"] == 1  # the query's one device sync
    named = [(name, s) for name, s in red.gaps if s > 5e-4]
    assert named and all(name.startswith("union.") for name, _ in named)
    inside = sum(s for name, s in red.idle_by_span.items() if name.startswith("union."))
    assert inside > 0.95 * red.idle_s
    assert set(sp.span_metrics(red)) == set(sp.METRICS)
    # the benchmark's own reduction of the same trace: three steps, one
    # engine core, gaps still unnamed (it keeps only bench.* spans)
    ref = tr.reduce_profile(profile)
    assert ref.modules_matching("serve_step")[0] == 3
    assert ref.modules_matching("jit_core")[0] == 1
    assert ref.idle_share == pytest.approx(red.idle_s / red.window_s, rel=1e-9)
    assert {name for name, _ in ref.top_gaps(10)} == {"host"}
