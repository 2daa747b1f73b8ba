"""Fault-tolerant concurrent sweep executor (``repro.core.sweep_exec``):
retry/deadline/degradation fault matrix, crash-safe journal resume
(including a real SIGKILL + byte-identity check), and the underlying
watchdog/retry primitives from ``repro.runtime.fault_tolerance``."""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from repro.core.architecture import edge_accelerator
from repro.core.cost import ResultStore
from repro.core.cost.store import SweepJournal
from repro.core.optimizer import SweepTask, union_opt_sweep
from repro.core.problem import Problem
from repro.core.sweep_exec import FaultSpec, task_fingerprint
from repro.runtime.fault_tolerance import (
    CallTimeoutError,
    CircuitBreaker,
    RetryPolicy,
    RetryStats,
    StragglerMeter,
    backoff_delay,
    call_with_deadline,
    retry_call,
)

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _tasks():
    """3 groups (distinct problems) x 2 tasks each; small enough that the
    whole matrix runs in a couple of seconds."""
    tasks = []
    for i, (m, n, k) in enumerate([(64, 64, 64), (128, 64, 32), (96, 48, 64)]):
        p = Problem.gemm(m, n, k, name=f"sweepexec-g{i}")
        arch = edge_accelerator(aspect=(16, 16))
        tasks.append(SweepTask(p, arch, mapper="random", cost_model="timeloop",
                               metric="edp", mapper_kw={"samples": 200}))
        tasks.append(SweepTask(p, arch, mapper="heuristic",
                               cost_model="timeloop", metric="edp"))
    return tasks


def _shape(sweep):
    """Comparable view of a sweep's solutions: cost + mapping only."""
    return [(s.cost.edp, s.mapping.to_dict()) for s in sweep]


# ------------------------------------------------------------------ #
# fault-spec grammar
# ------------------------------------------------------------------ #
def test_fault_spec_parse_and_checks():
    fs = FaultSpec.parse("fail:1@0; hang:2@1:0.25; jaxfail:0; kill-after:3")
    with pytest.raises(RuntimeError):
        fs.check_fail(1, 0)
    fs.check_fail(1, 1)  # only attempt 0 fails
    fs.check_fail(0, 0)
    assert fs.hang_s(2, 1) == 0.25
    assert fs.hang_s(2, 0) == 0.0
    assert fs.hang_s(0, 0) == 0.0
    assert 0 in fs.jaxfail and 1 not in fs.jaxfail
    assert fs.kill_after == 3
    # hang without explicit seconds gets the default
    assert FaultSpec.parse("hang:0@0").hang_s(0, 0) == 5.0
    empty = FaultSpec.parse(None)
    assert not empty.fails and not empty.hangs and empty.kill_after is None


def test_fault_spec_rejects_bad_clause():
    with pytest.raises(ValueError):
        FaultSpec.parse("explode:1@0")
    with pytest.raises(ValueError):
        FaultSpec.parse("fail:one@0")


def test_slow_spec_parse_and_accessor():
    fs = FaultSpec.parse("slow:1@0:0.25; slow:2@1")
    assert fs.slow_s(1, 0) == 0.25
    assert fs.slow_s(1, 1) == 0.0  # only attempt 0 is slowed
    assert fs.slow_s(2, 1) == 1.0  # default seconds
    assert fs.slow_s(0, 0) == 0.0
    empty = FaultSpec.parse(None)
    assert not empty.slows and empty.slow_s(0, 0) == 0.0


def test_slow_injection_completes_and_converges_to_baseline():
    """``slow`` stretches a group's wall clock but never its results:
    unlike ``hang`` the work COMPLETES, so no retry/timeout machinery
    fires and the sweep is bit-identical to the unslowed baseline."""
    tasks = _tasks()
    baseline = union_opt_sweep(tasks)
    t0 = time.monotonic()
    slowed = union_opt_sweep(tasks, fault_spec="slow:1@0:0.4")
    wall = time.monotonic() - t0
    assert _shape(slowed) == _shape(baseline)
    assert slowed.stats["retries"] == 0
    assert slowed.stats["timeouts"] == 0
    assert wall >= 0.4  # the injected latency really was served


# ------------------------------------------------------------------ #
# failure matrix: every injected path converges to baseline results
# ------------------------------------------------------------------ #
def test_injected_fail_and_hang_converge_to_baseline():
    tasks = _tasks()
    baseline = union_opt_sweep(tasks)
    faulty = union_opt_sweep(
        tasks,
        fault_spec="fail:1@0;hang:2@0:2",
        group_timeout_s=0.5,
        max_group_retries=2,
        group_backoff_s=0.0,
    )
    assert _shape(faulty) == _shape(baseline)
    st = faulty.stats
    assert st["retries"] >= 2  # one for the raise, one for the hang
    assert st["timeouts"] >= 1
    assert st["attempts"] >= len(st["group_wall"]) + 2


def test_fail_spec_exhausts_retry_budget():
    tasks = _tasks()
    with pytest.raises(RuntimeError, match="injected failure"):
        union_opt_sweep(tasks, fault_spec="fail:0@0;fail:0@1",
                        max_group_retries=1, group_backoff_s=0.0)


def test_thread_pool_matches_serial():
    tasks = _tasks()
    serial = union_opt_sweep(tasks, workers=1)
    threaded = union_opt_sweep(tasks, workers=2, pool="thread")
    assert _shape(threaded) == _shape(serial)
    assert threaded.stats["pool"] == "thread"
    assert serial.stats["pool"] == "serial"


def test_jax_sweeps_stay_in_one_process():
    # the chip belongs to one process: auto picks threads for jax, and an
    # explicit process pool is refused before anything is spawned
    tasks = _tasks()
    threaded = union_opt_sweep(tasks, engine_backend="jax", workers=2)
    assert threaded.stats["pool"] == "thread"
    assert threaded.stats["backend_fallbacks"] == 0
    assert _shape(threaded) == _shape(union_opt_sweep(tasks, workers=1))
    with pytest.raises(ValueError, match="one process"):
        union_opt_sweep(tasks, engine_backend="jax", workers=2, pool="process")


def test_jax_failure_degrades_to_numpy_bit_identical(monkeypatch):
    tasks = _tasks()
    baseline = union_opt_sweep(tasks, engine_backend="numpy")
    monkeypatch.setenv("UNION_FAULT_JAX", "1")
    degraded = union_opt_sweep(tasks, engine_backend="jax")
    assert _shape(degraded) == _shape(baseline)
    assert degraded.stats["backend_fallbacks"] >= len(
        degraded.stats["group_wall"]
    )
    assert degraded.stats["engine_backend"] == "jax"  # what was REQUESTED


def test_jaxfail_spec_hits_only_named_group(monkeypatch):
    # spec-level injection flips one group's ctx, not the global env
    tasks = _tasks()
    baseline = union_opt_sweep(tasks, engine_backend="numpy")
    degraded = union_opt_sweep(tasks, engine_backend="jax",
                               fault_spec="jaxfail:0")
    assert _shape(degraded) == _shape(baseline)
    assert degraded.stats["backend_fallbacks"] >= 1


# ------------------------------------------------------------------ #
# journal + resume
# ------------------------------------------------------------------ #
def test_journal_resume_replays_groups(tmp_path):
    tasks = _tasks()
    jpath = tmp_path / "sweep_journal.json"
    first = union_opt_sweep(tasks, journal=str(jpath))
    assert jpath.exists()
    resumed = union_opt_sweep(tasks, journal=str(jpath), resume=True)
    assert _shape(resumed) == _shape(first)
    assert resumed.stats["replayed_groups"] == len(first.stats["group_wall"])
    # replayed search stats match byte-for-byte in deterministic mode
    os.environ["UNION_DETERMINISTIC_STATS"] = "1"
    try:
        assert [s.search.stats_dict() for s in resumed] == [
            s.search.stats_dict() for s in first
        ]
    finally:
        del os.environ["UNION_DETERMINISTIC_STATS"]


def test_journal_without_resume_starts_fresh(tmp_path):
    tasks = _tasks()
    jpath = tmp_path / "sweep_journal.json"
    union_opt_sweep(tasks, journal=str(jpath))
    fresh = union_opt_sweep(tasks, journal=str(jpath))  # no resume
    assert fresh.stats["replayed_groups"] == 0


def test_corrupt_journal_discarded(tmp_path):
    jpath = tmp_path / "bad_journal.json"
    jpath.write_text("{not json")
    j = SweepJournal(jpath, resume=True)
    assert j.corrupt == 1 and not j.resumed
    assert not j.groups and not j.tasks
    jpath.write_text(json.dumps({"version": 999, "groups": {}, "tasks": {}}))
    j = SweepJournal(jpath, resume=True)
    assert j.corrupt == 1 and not j.resumed


_DRIVER = '''
import json, sys
sys.path.insert(0, {src!r})
from repro.core.architecture import edge_accelerator
from repro.core.cost import ResultStore
from repro.core.optimizer import SweepTask, union_opt_sweep
from repro.core.problem import Problem

def main():
    out, journal, store_dir, resume = sys.argv[1:5]
    tasks = []
    for i, (m, n, k) in enumerate(
        [(64, 64, 64), (128, 64, 32), (96, 48, 64), (80, 80, 40)]
    ):
        p = Problem.gemm(m, n, k, name=f"killres-g{{i}}")
        tasks.append(SweepTask(p, edge_accelerator(aspect=(16, 16)),
                               mapper="random", cost_model="timeloop",
                               metric="edp", mapper_kw={{"samples": 300}}))
    store = ResultStore(store_dir) if store_dir != "-" else None
    sweep = union_opt_sweep(tasks, result_store=store,
                            journal=None if journal == "-" else journal,
                            resume=resume == "1")
    rows = [{{"edp": s.cost.edp, "mapping": s.mapping.to_dict(),
              "search": s.search.stats_dict()}} for s in sweep]
    with open(out, "w") as f:
        json.dump({{"rows": rows, "sweep": sweep.stats}}, f, indent=1)
    if store is not None:
        store.flush()
        with open(out + ".store", "w") as f:
            json.dump(store.stats_dict(), f)

if __name__ == "__main__":
    main()
'''


def _run_driver(script, args, env_extra, cwd):
    env = dict(os.environ, UNION_DETERMINISTIC_STATS="1", **env_extra)
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    return subprocess.run([sys.executable, str(script)] + args, env=env,
                          cwd=cwd, capture_output=True, text=True,
                          timeout=300)


def test_sigkill_then_resume_is_byte_identical(tmp_path):
    """The acceptance drill: a sweep SIGKILLed right after its 2nd
    journal flush, resumed with the same journal + store, must emit
    byte-identical figure JSON to an uninterrupted run -- and the resumed
    half must run WARM against the store the killed run populated."""
    script = tmp_path / "driver.py"
    script.write_text(_DRIVER.format(src=SRC))
    jpath, spath = str(tmp_path / "journal.json"), str(tmp_path / "store")

    r = _run_driver(script, ["ref.json", "-", "-", "0"], {}, tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]

    r = _run_driver(script, ["never.json", jpath, spath, "0"],
                    {"UNION_FAULT_SPEC": "kill-after:2"}, tmp_path)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr[-2000:])
    assert Path(jpath).exists()
    assert not (tmp_path / "never.json").exists()

    # kill-after:2 fires between the 2nd group's store flush and its
    # journal record -- the journal holds 1 done group, the store holds 2
    r = _run_driver(script, ["resumed.json", jpath, spath, "1"], {}, tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "replaying 1/4" in (r.stdout + r.stderr)

    ref = (tmp_path / "ref.json").read_bytes()
    resumed = (tmp_path / "resumed.json").read_bytes()
    assert ref == resumed
    store_stats = json.loads((tmp_path / "resumed.json.store").read_text())
    assert store_stats["hits"] > 0  # killed run's flushed Costs were reused


# ------------------------------------------------------------------ #
# store hardening
# ------------------------------------------------------------------ #
def test_stale_store_tmp_cleaned_at_flush(tmp_path):
    sdir = tmp_path / "store"
    sdir.mkdir()
    stale = sdir / ".deadspace.999.cafef00d.tmp"
    stale.write_text("{}")
    store = ResultStore(sdir)
    tasks = _tasks()[:1]
    union_opt_sweep(tasks, result_store=store)
    store.flush()
    assert not stale.exists()
    assert store.stats_dict()["stale_tmps"] >= 1


# ------------------------------------------------------------------ #
# fingerprints
# ------------------------------------------------------------------ #
def test_task_fingerprint_stable_and_slot_unique():
    p = Problem.gemm(64, 64, 64, name="fp")
    arch = edge_accelerator(aspect=(16, 16))
    f0 = task_fingerprint("gk", p, arch, ("random", {"samples": 10}),
                         None, None, 0)
    assert f0 == task_fingerprint("gk", p, arch, ("random", {"samples": 10}),
                                  None, None, 0)
    assert f0 != task_fingerprint("gk", p, arch, ("random", {"samples": 10}),
                                  None, None, 1)
    assert f0 != task_fingerprint("gk", p, arch, ("random", {"samples": 11}),
                                  None, None, 0)
    # set-valued fields canonicalize: equal sets, equal fingerprints
    fa = task_fingerprint("gk", p, arch, ("random", {"dims": {"a", "b", "c"}}),
                          None, None, 0)
    fb = task_fingerprint("gk", p, arch, ("random", {"dims": {"c", "b", "a"}}),
                          None, None, 0)
    assert fa == fb


# ------------------------------------------------------------------ #
# watchdog/retry primitives
# ------------------------------------------------------------------ #
def test_retry_call_retries_then_succeeds():
    stats = RetryStats()
    seen = []

    def fn(attempt):
        seen.append(attempt)
        if attempt < 2:
            raise RuntimeError("flaky")
        return "ok"

    out, _ = retry_call(fn, RetryPolicy(max_retries=3, backoff_s=0.0),
                        label="t", stats=stats)
    assert out == "ok"
    assert seen == [0, 1, 2]
    assert stats.retries == 2 and stats.attempts == 3
    assert stats.timeouts == 0


def test_retry_call_exhausts_and_raises():
    stats = RetryStats()

    def fn(attempt):
        raise RuntimeError(f"always (attempt {attempt})")

    with pytest.raises(RuntimeError, match="always"):
        retry_call(fn, RetryPolicy(max_retries=2, backoff_s=0.0),
                   label="t", stats=stats)
    assert stats.attempts == 3 and stats.retries == 2
    assert len(stats.errors) == 3


def test_call_with_deadline_times_out():
    with pytest.raises(CallTimeoutError):
        call_with_deadline(lambda: time.sleep(2), 0.1, label="hang")
    assert call_with_deadline(lambda: 42, 5.0, label="fast") == 42
    assert call_with_deadline(lambda: 7, None, label="inline") == 7


def test_backoff_delay_is_deterministic_and_label_diverse():
    pol = RetryPolicy(max_retries=3, backoff_s=0.1, jitter=0.25)
    a1 = backoff_delay(pol, 1, "group0")
    assert a1 == backoff_delay(pol, 1, "group0")  # deterministic
    assert a1 != backoff_delay(pol, 1, "group1")  # labels de-synchronize
    assert backoff_delay(pol, 2, "group0") > 0
    assert backoff_delay(RetryPolicy(backoff_s=0.0), 1, "x") == 0.0


def test_circuit_breaker_opens_after_threshold():
    br = CircuitBreaker(failure_threshold=3, probe_interval=2)
    assert br.state == CircuitBreaker.CLOSED
    br.record_failure()
    br.record_failure()
    assert br.state == CircuitBreaker.CLOSED  # below threshold
    assert br.allow()
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    assert br.opened == 1
    assert br.transitions == ["closed->open"]


def test_circuit_breaker_success_resets_consecutive_count():
    br = CircuitBreaker(failure_threshold=2, probe_interval=2)
    br.record_failure()
    br.record_success()  # streak broken
    br.record_failure()
    assert br.state == CircuitBreaker.CLOSED
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN


def test_circuit_breaker_probe_schedule_is_count_based():
    br = CircuitBreaker(failure_threshold=1, probe_interval=3)
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    # denied, denied, then the 3rd call is admitted as the probe
    assert br.allow() is False
    assert br.allow() is False
    assert br.allow() is True
    assert br.state == CircuitBreaker.HALF_OPEN
    assert br.probes == 1 and br.denied == 2
    # only ONE probe in flight: further calls are denied while half-open
    assert br.allow() is False
    br.record_success()
    assert br.state == CircuitBreaker.CLOSED
    assert br.recovered == 1
    assert br.transitions == [
        "closed->open", "open->half_open", "half_open->closed"
    ]


def test_circuit_breaker_failed_probe_reopens():
    br = CircuitBreaker(failure_threshold=1, probe_interval=1)
    br.record_failure()
    assert br.allow() is True  # probe admitted immediately (interval 1)
    br.record_failure()  # the probe lost
    assert br.state == CircuitBreaker.OPEN
    assert br.opened == 2
    # the schedule restarts: the next allow is a fresh probe
    assert br.allow() is True
    br.record_success()
    assert br.state == CircuitBreaker.CLOSED


def test_circuit_breaker_cooldown_uses_injected_clock():
    now = [0.0]
    br = CircuitBreaker(failure_threshold=1, probe_interval=1,
                        cooldown_s=10.0, clock=lambda: now[0])
    br.record_failure()
    assert br.allow() is False  # inside the cooldown window
    now[0] = 10.5
    assert br.allow() is True  # cooldown elapsed -> count-based probe
    assert br.state == CircuitBreaker.HALF_OPEN


def test_circuit_breaker_rejects_bad_params_and_caps_transitions():
    with pytest.raises(ValueError):
        CircuitBreaker(failure_threshold=0)
    with pytest.raises(ValueError):
        CircuitBreaker(probe_interval=0)
    br = CircuitBreaker(failure_threshold=1, probe_interval=1)
    for _ in range(100):  # open -> half-open -> open forever
        br.record_failure()
        br.allow()
    assert len(br.transitions) <= 64
    st = br.stats_dict()
    assert st["state"] == br.state and st["opened"] == br.opened


def test_straggler_meter_flags_outliers():
    m = StragglerMeter(window=10, slack=3.0)
    assert m.note(1.0) is False  # no history yet
    for _ in range(5):
        assert m.note(1.0) is False
    assert m.note(10.0) is True
    assert m.flagged == 1
    assert m.note(1.0) is False  # the outlier raised the average, 1.0 is fine


# ------------------------------------------------------------------ #
# deterministic stats mode
# ------------------------------------------------------------------ #
def test_deterministic_stats_subset(monkeypatch):
    tasks = _tasks()[:2]
    sweep = union_opt_sweep(tasks)
    full = sweep[0].search.stats_dict()
    assert "elapsed_s" in full and "evaluated" in full
    monkeypatch.setenv("UNION_DETERMINISTIC_STATS", "1")
    det = sweep[0].search.stats_dict()  # stats_dict reads the env per call
    assert set(det) == {"considered", "backend_fallbacks", "elapsed_s",
                        "evals_per_s"}
    assert det["elapsed_s"] == 0.0 and det["evals_per_s"] == 0.0
    assert det["considered"] == full["considered"]
    # the sweep-level aggregate is fixed at run time: a det-mode RUN
    # strips the run-variant ledger (walls, timings)
    det_sweep = union_opt_sweep(tasks)
    agg = det_sweep.stats
    assert agg["elapsed_s"] == 0.0
    assert "group_wall" not in agg  # walls are run-variant
