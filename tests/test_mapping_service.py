"""Mapping-as-a-service daemon (``repro.serve.mapping_service``):
query parsing + fingerprints, cold->warm byte-identity, deadline-capped
partial answers, nearest-neighbor warm starts, the jax circuit-breaker
recovery cycle, HTTP backpressure, and the two subprocess drills --
SIGTERM graceful drain and kill -9 + restart byte-identity."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from repro.core.architecture import edge_accelerator
from repro.core.optimizer import COST_MODEL_REGISTRY
from repro.core.problem import Problem
from repro.serve.mapping_service import (
    MappingService,
    QueryError,
    _ParsedQuery,
    _slice_plan,
    query_fingerprint,
    serve,
)

SRC = str(Path(__file__).resolve().parent.parent / "src")


def _gemm_query(m, n, k, *, budget=120, deadline_s=None, metric="edp",
                name=None, **extra):
    q = {
        "problem": {"kind": "gemm", "m": m, "n": n, "k": k},
        "arch": {"kind": "edge", "aspect": [16, 16]},
        "metric": metric,
        "mapper": {"name": "random", "kw": {"seed": 7}},
        "budget": budget,
    }
    if deadline_s is not None:
        q["deadline_s"] = deadline_s
    if name is not None:
        q["problem"]["name"] = name
    q.update(extra)
    return q


def _rec_bytes(env):
    return json.dumps(env["record"], sort_keys=True).encode()


# ------------------------------------------------------------------ #
# parsing + fingerprints
# ------------------------------------------------------------------ #
def test_query_fingerprint_stable_and_deadline_excluded():
    cm = COST_MODEL_REGISTRY["timeloop"]()
    p = Problem.gemm(64, 32, 16, name="fp-a")
    arch = edge_accelerator(aspect=(16, 16))
    f0 = query_fingerprint(cm, p, arch, "edp", "random", {"seed": 7}, 100)
    assert f0 == query_fingerprint(cm, p, arch, "edp", "random",
                                   {"seed": 7}, 100)
    assert f0 != query_fingerprint(cm, p, arch, "edp", "random",
                                   {"seed": 8}, 100)
    assert f0 != query_fingerprint(cm, p, arch, "latency", "random",
                                   {"seed": 7}, 100)
    assert f0 != query_fingerprint(cm, p, arch, "edp", "random",
                                   {"seed": 7}, 101)
    # display names never affect costs, so they never affect fingerprints
    p2 = Problem.gemm(64, 32, 16, name="fp-OTHER")
    assert f0 == query_fingerprint(cm, p2, arch, "edp", "random",
                                   {"seed": 7}, 100)
    # answers searched on an accelerator are kept apart from the host's
    f_tpu = query_fingerprint(cm, p, arch, "edp", "random", {"seed": 7}, 100,
                              "tpu")
    assert f_tpu != f0
    assert _ParsedQuery(_gemm_query(64, 32, 16), 5.0, "tpu").fingerprint != (
        _ParsedQuery(_gemm_query(64, 32, 16), 5.0).fingerprint
    )
    # the deadline shapes search time, not the converged answer
    qa = _ParsedQuery(_gemm_query(64, 32, 16), 5.0)
    qb = _ParsedQuery(_gemm_query(64, 32, 16, deadline_s=0.25), 5.0)
    assert qa.fingerprint == qb.fingerprint
    assert qb.deadline_s == 0.25


@pytest.mark.parametrize(
    "mutate",
    [
        {"problem": {"kind": "wavelet"}},
        {"problem": {"kind": "gemm", "m": 64, "n": 32}},  # k missing
        {"metric": "carbon"},
        {"mapper": "annealing-imaginary"},
        {"budget": "lots"},
        {"deadline_s": -1},
        {"arch": {"kind": "dyson-sphere"}},
        {"model": "no-such-model"},
    ],
    ids=["kind", "missing-dim", "metric", "mapper", "budget", "deadline",
         "arch", "model"],
)
def test_malformed_queries_raise_query_error(mutate):
    q = _gemm_query(64, 32, 16)
    q.update(mutate)
    with pytest.raises(QueryError):
        _ParsedQuery(q, 5.0)


def test_slice_plan_covers_budget_exactly():
    for total in (1, 63, 64, 65, 320, 512, 1000):
        plan = _slice_plan(total)
        assert sum(plan) == total
        assert all(s > 0 for s in plan)
        assert plan[0] <= 64  # a tight deadline still finishes slice 0


# ------------------------------------------------------------------ #
# in-process service: cold -> warm -> restart
# ------------------------------------------------------------------ #
def test_cold_then_warm_then_restart_byte_identical(tmp_path):
    svc = MappingService(str(tmp_path), deadline_s=None)
    q = _gemm_query(64, 48, 32)
    cold = svc.handle_query(q)
    assert cold["ok"] and cold["source"] == "search"
    assert not cold["budget_exhausted"]
    warm = svc.handle_query(q)
    assert warm["ok"] and warm["source"] == "store"
    assert _rec_bytes(warm) == _rec_bytes(cold)
    # same content under a different display name: same answer, no search
    renamed = svc.handle_query(_gemm_query(64, 48, 32, name="alias"))
    assert renamed["source"] == "store"
    assert _rec_bytes(renamed) == _rec_bytes(cold)
    m = svc.metrics()
    assert m["queries"] == 3 and m["store_hits"] == 2 and m["searches"] == 1
    svc.drain()
    # a NEW service on the same state dir answers from the journal alone
    svc2 = MappingService(str(tmp_path), deadline_s=None)
    again = svc2.handle_query(q)
    assert again["source"] == "store"
    assert _rec_bytes(again) == _rec_bytes(cold)
    assert svc2.metrics()["searches"] == 0


def test_error_envelope_not_exception(tmp_path):
    svc = MappingService(str(tmp_path))
    env = svc.handle_query({"problem": {"kind": "wavelet"}})
    assert env["ok"] is False and "wavelet" in env["error"]
    assert svc.metrics()["errors"] == 1
    assert svc.metrics()["queries"] == 0  # rejected before admission


# ------------------------------------------------------------------ #
# deadlines: partial answers, never errors
# ------------------------------------------------------------------ #
def test_tiny_deadline_returns_flagged_fallback(tmp_path):
    svc = MappingService(str(tmp_path))
    env = svc.handle_query(_gemm_query(96, 96, 96, budget=5000,
                                       deadline_s=1e-4))
    assert env["ok"] is True
    assert env["budget_exhausted"] is True
    assert env["record"]["mapping"]  # an incumbent, not an error
    assert env["record"]["cost"]  # a scored Cost record rides along
    m = svc.metrics()
    assert m["partials"] == 1 and m["fallback_answers"] == 1
    # partial answers are NOT journaled: the query stays cold
    again = svc.handle_query(_gemm_query(96, 96, 96, budget=5000,
                                         deadline_s=None))
    assert again["source"] == "search" and not again["budget_exhausted"]


def test_slow_injection_yields_partial_with_real_incumbent(tmp_path):
    """``slow:0@1:30`` stalls budget slice 1 of cold search 0; with a
    ~1s deadline the answer is slice 0's real incumbent, flagged
    exhausted -- the deadline path fires without any wall-clock
    guesswork."""
    svc = MappingService(str(tmp_path), fault_spec="slow:0@1:30")
    env = svc.handle_query(_gemm_query(80, 80, 40, budget=512,
                                       deadline_s=1.0))
    assert env["ok"] is True and env["budget_exhausted"] is True
    assert env["record"]["counters"]["considered"] >= 64  # slice 0 ran
    m = svc.metrics()
    assert m["partials"] == 1
    assert m["fallback_answers"] == 0  # real incumbent, not the fallback
    # a re-ask without the deadline converges and journals normally
    done = svc.handle_query(_gemm_query(80, 80, 40, budget=512,
                                        deadline_s=None))
    assert done["source"] == "search" and not done["budget_exhausted"]
    assert svc.handle_query(
        _gemm_query(80, 80, 40, budget=512)
    )["source"] == "store"


# ------------------------------------------------------------------ #
# nearest-neighbor warm starts
# ------------------------------------------------------------------ #
def test_neighbor_seed_fires_and_result_matches_unseeded(tmp_path):
    svc = MappingService(str(tmp_path), deadline_s=None)
    first = svc.handle_query(_gemm_query(64, 64, 64))
    assert first["seeded"] is False  # nothing registered yet
    near = svc.handle_query(_gemm_query(64, 64, 48))
    assert near["seeded"] is True
    assert near["neighbor"]["distance"] >= 0.0
    m = svc.metrics()
    assert m["seeded"] == 1 and m["neighbor_hits"] == 1
    assert m["neighbor_misses"] == 1
    # seeding is a pruning accelerant, never an answer-changer: the same
    # query against a fresh (seedless) state dir finds the same best
    lone = MappingService(str(tmp_path / "lone"), deadline_s=None)
    ref = lone.handle_query(_gemm_query(64, 64, 48))
    assert near["record"]["cost"] == ref["record"]["cost"]
    assert near["record"]["mapping"] == ref["record"]["mapping"]


# ------------------------------------------------------------------ #
# circuit breaker: open -> half-open -> closed under injected jax faults
# ------------------------------------------------------------------ #
def test_breaker_opens_degrades_and_recovers(tmp_path):
    svc = MappingService(
        str(tmp_path), backend="jax", deadline_s=None,
        breaker_threshold=2, probe_interval=2,
        fault_spec="jaxfail:0;jaxfail:1",
    )
    envs = [svc.handle_query(_gemm_query(32 + 16 * i, 32, 32, budget=96))
            for i in range(4)]
    assert all(e["ok"] for e in envs)
    br = svc.metrics()["breaker"]
    assert br["transitions"] == [
        "closed->open", "open->half_open", "half_open->closed"
    ]
    assert br["state"] == "closed"
    assert br["opened"] == 1 and br["recovered"] == 1
    # queries 0/1 degraded mid-search; 2 was denied jax (circuit open);
    # 3 was the half-open probe that ran clean and closed the circuit
    assert envs[0]["backend"] == "numpy"
    assert envs[2]["backend"] == "numpy"
    assert envs[3]["backend"] == "jax"


def test_breaker_open_answers_stay_available_numpy(tmp_path):
    """With the circuit held open (every query's jax poisoned), answers
    keep flowing on the numpy path -- degradation is invisible to the
    caller apart from the advertised backend."""
    svc = MappingService(
        str(tmp_path), backend="jax", deadline_s=None, breaker_threshold=1,
        probe_interval=100, fault_spec=";".join(f"jaxfail:{i}"
                                                for i in range(4)),
    )
    for i in range(4):
        env = svc.handle_query(_gemm_query(48 + 16 * i, 32, 32, budget=96))
        assert env["ok"] and env["record"]["mapping"]
    br = svc.metrics()["breaker"]
    assert br["state"] == "open" and br["denied"] >= 1


# ------------------------------------------------------------------ #
# HTTP front: round-trip, 400, and deterministic 429 backpressure
# ------------------------------------------------------------------ #
def _post(port, payload, timeout=60.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/mapping",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_http_round_trip_and_metrics(tmp_path):
    svc = MappingService(str(tmp_path), deadline_s=None, workers=1)
    httpd = serve(svc)
    port = httpd.server_address[1]
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    try:
        st, env, _ = _post(port, _gemm_query(64, 32, 32))
        assert st == 200 and env["ok"] and env["source"] == "search"
        st, warm, _ = _post(port, _gemm_query(64, 32, 32))
        assert st == 200 and warm["source"] == "store"
        assert _rec_bytes(warm) == _rec_bytes(env)
        st, bad, _ = _post(port, {"problem": {"kind": "wavelet"}})
        assert st == 400 and bad["ok"] is False
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ) as r:
            m = json.loads(r.read())
        assert m["queries"] == 2 and m["store_hits"] == 1
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30
        ) as r:
            assert json.loads(r.read()) == {"ok": True, "draining": False}
    finally:
        httpd.shutdown()
        svc.drain()


def test_http_queue_full_sheds_with_retry_after(tmp_path):
    """Deterministic backpressure: no workers running yet, queue cap 1 --
    the first POST parks in the queue, the second MUST be shed with 429 +
    Retry-After. Workers are then started so the parked job completes."""
    svc = MappingService(str(tmp_path), deadline_s=None, queue_cap=1,
                         workers=1)
    from repro.serve.mapping_service import _make_handler
    from http.server import ThreadingHTTPServer

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _make_handler(svc))
    httpd.daemon_threads = True
    port = httpd.server_address[1]
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    first = {}

    def poster():
        first["out"] = _post(port, _gemm_query(64, 32, 32), timeout=180.0)

    pt = threading.Thread(target=poster, daemon=True)
    pt.start()
    deadline = time.monotonic() + 10.0
    while svc.jobs.qsize() < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert svc.jobs.qsize() == 1
    st, env, headers = _post(port, _gemm_query(48, 32, 32))
    assert st == 429
    assert env["error"] == "admission queue full"
    assert headers.get("Retry-After") == "1"
    assert svc.metrics()["shed"] == 1
    svc.start_workers()  # release the parked job
    pt.join(timeout=120.0)
    assert not pt.is_alive()
    st, env, _ = first["out"]
    assert st == 200 and env["ok"]
    httpd.shutdown()
    svc.drain()


# ------------------------------------------------------------------ #
# subprocess drills: SIGTERM drain, kill -9 + restart byte-identity
# ------------------------------------------------------------------ #
def _spawn_daemon(state_dir, *extra_args, timeout_s=60.0):
    ready = os.path.join(state_dir, "ready.json")
    if os.path.exists(ready):  # stale file from a previous incarnation
        os.unlink(ready)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve.mapping_service",
         "--state-dir", str(state_dir), "--ready-file", ready,
         "--deadline-s", "0", *extra_args],
        env=env,
    )
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if os.path.exists(ready):
            with open(ready) as f:
                return proc, json.load(f)["port"]
        if proc.poll() is not None:
            raise AssertionError(f"daemon died at startup rc={proc.returncode}")
        time.sleep(0.05)
    proc.kill()
    raise AssertionError("daemon never became ready")


def test_sigterm_drains_inflight_query_and_exits_zero(tmp_path):
    """The graceful half of crash safety: SIGTERM while a cold query is
    in flight -- the query is still answered AND journaled (a restarted
    daemon serves it warm), and the daemon exits 0."""
    proc, port = _spawn_daemon(tmp_path)
    q = _gemm_query(72, 72, 36, budget=400)
    out = {}

    def poster():
        out["resp"] = _post(port, q, timeout=120.0)

    pt = threading.Thread(target=poster, daemon=True)
    pt.start()
    time.sleep(0.15)  # let the POST be admitted
    proc.send_signal(signal.SIGTERM)
    pt.join(timeout=120.0)
    assert not pt.is_alive()
    st, env, _ = out["resp"]
    assert st == 200 and env["ok"], env
    assert proc.wait(timeout=60.0) == 0  # clean drain exit

    proc2, port2 = _spawn_daemon(tmp_path)
    try:
        st, warm, _ = _post(port2, q)
        assert st == 200 and warm["source"] == "store"
        assert _rec_bytes(warm) == _rec_bytes(env)
    finally:
        proc2.send_signal(signal.SIGTERM)
        assert proc2.wait(timeout=60.0) == 0


def test_kill9_restart_answers_byte_identical_from_store(tmp_path):
    """The acceptance drill: answer queries, kill -9 the daemon, restart
    on the same state dir -- every previously-answered query must come
    back byte-identical from the journal with ZERO re-search
    (store_hits == queries)."""
    proc, port = _spawn_daemon(tmp_path)
    queries = [_gemm_query(64 + 16 * i, 64, 32, budget=150) for i in range(3)]
    before = []
    for q in queries:
        st, env, _ = _post(port, q, timeout=120.0)
        assert st == 200 and env["ok"] and env["source"] == "search"
        before.append(env)
    proc.kill()  # SIGKILL: no drain, no atexit, nothing graceful
    assert proc.wait(timeout=30.0) == -signal.SIGKILL

    proc2, port2 = _spawn_daemon(tmp_path)
    try:
        for q, old in zip(queries, before):
            st, env, _ = _post(port2, q, timeout=120.0)
            assert st == 200 and env["source"] == "store"
            assert _rec_bytes(env) == _rec_bytes(old)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port2}/metrics", timeout=30
        ) as r:
            m = json.loads(r.read())
        assert m["queries"] == len(queries)
        assert m["store_hits"] == m["queries"]  # zero re-search
        assert m["searches"] == 0
        assert m["journal"]["resumed"] is True
    finally:
        proc2.send_signal(signal.SIGTERM)
        assert proc2.wait(timeout=60.0) == 0
