"""Unified tracing + metrics layer (ISSUE 8): span propagation parity
across every transport, disabled-tracer no-op guarantees, the chaos
recovery span, Chrome-trace export validity, and the ``controller.stats``
migration onto incremental ``IntervalUnion`` aggregation (bit-compatible
with the legacy full-re-merge formula)."""
import os
import random
import time

import pytest

from repro.core import FaultPlan, spawn_actor
from repro.core.executor import Executor
from repro.core.controller import (_RunStats, _interval_overlap,
                                   _merge_intervals)
from repro.obs import trace as obs_trace
from repro.obs.__main__ import events_from_chrome, summarize
from repro.obs.metrics import (Histogram, IntervalUnion, MetricsRegistry,
                               interval_overlap)

from test_supervision import build_supervised


@pytest.fixture
def traced():
    """A fresh global tracer for the test, uninstalled afterwards so the
    rest of the suite keeps the zero-cost disabled path."""
    prior = obs_trace.disable()
    t = obs_trace.enable("controller")
    try:
        yield t
    finally:
        obs_trace.disable()
        if prior is not None:
            obs_trace.enable(prior.proc)


class TracedEcho(Executor):
    """Importable RPC target whose endpoint records into the *child's*
    tracer (proc/shm/socket) or straight into the parent's (inproc)."""

    role = "traced-echo"

    def ping2(self):
        obs_trace.instant("inside-ping", "test")
        return os.getpid()


# ----------------------------------------------------------- tracer core --

def test_disabled_tracer_is_shared_noop():
    assert not obs_trace.enabled()
    assert obs_trace.span("x", "cat", a=1) is obs_trace.NOOP_SPAN
    assert obs_trace.span("y") is obs_trace.span("z")   # one shared object
    obs_trace.instant("nothing")                        # all no-ops
    obs_trace.complete("nothing", "c", 0.0, 1.0)
    assert obs_trace.flow_start() is None
    obs_trace.flow_end(None)
    with obs_trace.span("x") as sp:
        assert sp.set(a=1) is sp
    assert obs_trace.tracer() is None


class _CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    label it is opened with."""

    made: list = []

    def __init__(self, name):
        self.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, et, ev, tb):
        return False


def test_disabled_span_opens_no_profiler_annotation(monkeypatch):
    import jax.profiler
    made = _CountingAnnotation.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    assert not obs_trace.enabled()
    sp = obs_trace.span("decode-round", "engine", rows=3)
    assert sp is obs_trace.NOOP_SPAN
    with sp:
        pass
    assert made == []
    # an enabled tracer names each live span's annotation by category
    t = obs_trace.Tracer("probe")
    with t.span("decode-round", "engine"):
        with t.span("bare"):
            pass
    assert made == ["repro:engine.decode-round", "repro:bare"]


def test_enabled_spans_land_on_their_thread_in_a_profiler_trace(traced,
                                                                tmp_path):
    """Nested spans opened on two threads while ``jax.profiler`` traces
    show up as ``repro:`` events, each thread's on a host line of its
    own, the inner one inside the outer one."""
    import glob
    import threading

    import jax
    from jax.profiler import ProfileData

    def work(tag):
        with obs_trace.span("outer", tag):
            with obs_trace.span("inner-wait", tag):
                time.sleep(0.005)

    jax.profiler.start_trace(str(tmp_path))
    try:
        th = threading.Thread(target=work, args=("worker",))
        th.start()
        work("main")
        th.join(timeout=10)
    finally:
        jax.profiler.stop_trace()
    assert not th.is_alive()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith("repro:")}
            if evs:
                lines[(plane.name, i)] = evs
    by_names = sorted(sorted(evs) for evs in lines.values())
    assert by_names == [["repro:main.inner-wait", "repro:main.outer"],
                        ["repro:worker.inner-wait", "repro:worker.outer"]]
    for evs in lines.values():
        (o0, o1), = [v for k, v in evs.items() if k.endswith(".outer")]
        (i0, i1), = [v for k, v in evs.items() if k.endswith("-wait")]
        assert o0 <= i0 < i1 <= o1
        assert i1 - i0 >= 5e6                     # the sleep, in ns
    # the ring buffer still records every span
    assert sorted(e[3] for e in traced.events() if e[2] == "X") == \
        ["inner-wait", "inner-wait", "outer", "outer"]


def test_span_records_complete_event_with_nesting(traced):
    with traced.span("outer", "t"):
        assert traced.current_span() == "outer"
        with traced.span("inner", "t", k=1):
            assert traced.current_span() == "inner"
    assert traced.current_span() is None
    evs = traced.events()
    names = [e[3] for e in evs if e[2] == "X"]
    assert names == ["inner", "outer"]                  # exit order
    inner = next(e for e in evs if e[3] == "inner")
    outer = next(e for e in evs if e[3] == "outer")
    assert inner[7] == {"k": 1}
    # inner's window sits inside outer's
    assert outer[5] <= inner[5] and \
        inner[5] + inner[6] <= outer[5] + outer[6] + 1e-9


def test_span_error_annotation_and_ring_buffer(traced):
    with pytest.raises(ValueError):
        with traced.span("boom", "t"):
            raise ValueError("x")
    ev = traced.events()[-1]
    assert ev[7]["error"] == "ValueError"
    small = obs_trace.Tracer("tiny", capacity=4)
    for i in range(7):
        small.instant(f"e{i}")
    assert len(small.events()) == 4 and small.dropped == 3
    assert [e[3] for e in small.events()] == ["e3", "e4", "e5", "e6"]


def test_chrome_export_roundtrip(traced, tmp_path):
    with traced.span("work", "cat", n=3):
        traced.instant("tick", "cat")
    fid = traced.flow_start()
    traced.flow_end(fid)
    path = tmp_path / "t.json"
    doc = obs_trace.export(str(path), metadata={"run": "test"})
    assert obs_trace.validate_chrome(doc) == []
    assert doc["metadata"]["run"] == "test"
    assert doc["metadata"]["trace_epoch_monotonic"] == obs_trace.epoch()
    back = events_from_chrome(doc)
    # proc/tid/ph/name/cat survive; timestamps within us quantization
    for orig, rt in zip(traced.events(), back):
        assert orig[:5] == rt[:5]
        assert rt[5] == pytest.approx(orig[5], abs=2e-6)
        assert rt[6] == pytest.approx(orig[6], abs=2e-6)
    s = summarize(back)
    assert s["phases"]["cat/work"]["count"] == 1
    assert s["instants"] == 1


# ------------------------------------------------- cross-process spans --

@pytest.mark.parametrize("transport", ["inproc", "proc", "shm", "socket"])
def test_span_propagation_parity_across_transports(traced, transport):
    """The same instrumented endpoint, driven over every transport:
    child-side events land in the parent's buffer, rebased onto the
    parent's epoch so the serve span sits inside the rpc span that
    caused it, with a matching flow arrow."""
    h = spawn_actor(TracedEcho, name=f"techo-{transport}",
                    transport=transport)
    try:
        for _ in range(2):
            assert isinstance(h.call("ping2"), int)
        h.drain_trace()
    finally:
        h.close()
    evs = traced.events()
    procs = {e[0] for e in evs}
    inside = [e for e in evs if e[3] == "inside-ping"]
    assert len(inside) == 2
    if transport == "inproc":
        assert procs == {"controller"}      # same process, same tracer
        return
    assert procs == {"controller", f"techo-{transport}"}
    rpcs = sorted((e for e in evs if e[3] == "rpc:ping2"),
                  key=lambda e: e[5])
    serves = sorted((e for e in evs if e[3] == "serve:ping2"),
                    key=lambda e: e[5])
    assert len(rpcs) >= 2 and len(serves) >= 2
    for rpc, srv in zip(rpcs, serves):
        assert rpc[0] == "controller" and srv[0] != "controller"
        # clock-sync alignment: the child's serve window sits inside the
        # parent's rpc window (generous slack for scheduler jitter)
        assert rpc[5] - 5e-3 <= srv[5]
        assert srv[5] + srv[6] <= rpc[5] + rpc[6] + 5e-3
    sids = {(e[7] or {}).get("id") for e in evs if e[2] == "s"}
    fids = {(e[7] or {}).get("id") for e in evs if e[2] == "f"}
    assert fids and fids <= sids            # every arrow head has a tail
    assert obs_trace.validate_chrome(obs_trace.to_chrome(evs)) == []


def test_disabled_rpc_ships_no_trace_frames():
    """With tracing off the wire protocol is untouched: no spans, no
    flow ids, nothing to drain from the child."""
    assert not obs_trace.enabled()
    h = spawn_actor(TracedEcho, name="techo-off", transport="proc")
    try:
        assert isinstance(h.call("ping2"), int)
        assert h.drain_trace() == 0
    finally:
        h.close()
    assert obs_trace.tracer() is None


def test_chaos_kill_produces_recovery_span_on_aligned_timeline(
        traced, tmp_path):
    """ISSUE 8 acceptance: a traced REPRO_CHAOS run over ProcTransport
    (pool of 2) exports valid Chrome JSON with spans from >= 3 distinct
    processes on one timeline, per-subscriber publish spans, and a
    recovery span whose duration matches the supervisor event log."""
    chaos = FaultPlan.parse("kill:generator1@batch=3")
    ctl = build_supervised(n_gens=2, staleness=1, max_steps=6,
                           transport="proc", chaos=chaos)
    hist = ctl.run()
    assert [h["step"] for h in hist] == list(range(6))
    respawns = ctl.supervisor.events("respawned")
    assert [e["actor"] for e in respawns] == ["generator1"]

    path = tmp_path / "chaos.json"
    doc = obs_trace.export(str(path))
    assert obs_trace.validate_chrome(doc) == []
    evs = traced.events()
    span_procs = {e[0] for e in evs if e[2] == "X"}
    assert {"controller", "generator0", "generator1"} <= span_procs

    # per-subscriber fabric publish spans for both pool workers
    pubs = {e[3] for e in evs if e[4] == "fabric"}
    assert {"publish:generator0", "publish:generator1"} <= pubs

    # the recovery span matches the supervisor's event log (same epoch)
    recs = [e for e in evs if e[3] == "recover" and e[4] == "supervisor"]
    assert len(recs) == 1
    rec = recs[0]
    assert rec[7]["actor"] == "generator1"
    assert rec[6] == pytest.approx(respawns[0]["recovery_s"], rel=1e-6)
    # ... and sits where the supervisor says it ended (unified clocks)
    assert rec[5] + rec[6] == pytest.approx(respawns[0]["t"], abs=0.05)

    s = summarize(evs)
    assert len(s["recoveries"]) == 1
    assert set(s["publish_by_subscriber"]) >= {"generator0", "generator1"}
    assert s["batch_latency"]["count"] == 6
    # history rows share the trace epoch too
    assert all(0.0 < h["t"] <= obs_trace.now() for h in hist)


# -------------------------------------------------------------- metrics --

def test_histogram_quantiles_are_bucket_upper_bounds():
    h = Histogram("lat", buckets=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.002, 0.003, 0.05, 2.5):
        h.observe(v)
    assert h.count == 5 and h.mean == pytest.approx(0.5111)
    assert h.quantile(0.5) == 0.01          # 3rd of 5 lands in (.001,.01]
    assert h.quantile(0.99) == 1.0          # overflow reports last bound
    assert Histogram("empty").quantile(0.5) == 0.0


def test_registry_instruments_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.0)
    reg.gauge("g").set(7.0)
    reg.histogram("h").observe(0.5)
    snap = reg.snapshot()
    assert snap["c"] == {"type": "counter", "value": 3.0}
    assert snap["g"]["value"] == 7.0
    assert snap["h"]["count"] == 1
    with pytest.raises(AssertionError):
        reg.gauge("c")                      # name/type collisions rejected


def test_interval_union_matches_legacy_merge():
    rng = random.Random(8)
    union = IntervalUnion()
    raw = []
    for _ in range(200):
        s = rng.uniform(0, 50)
        e = s + rng.uniform(0, 5)
        raw.append((s, e))
        union.add(s, e)
    merged = _merge_intervals(raw)
    assert union.intervals() == merged
    assert union.total == pytest.approx(sum(e - s for s, e in merged),
                                        abs=1e-9)
    other = IntervalUnion([(i * 3.0, i * 3.0 + 2.0) for i in range(40)])
    assert interval_overlap(union, other) == pytest.approx(
        _interval_overlap(merged, other.intervals()), abs=1e-9)


# ---------------------------------------------------- stats migration --

class _FakeFabric:
    def __init__(self):
        self.intervals = []


class _FakePool:
    def __init__(self):
        self.intervals = []


class _FakeCtl:
    def __init__(self):
        self.history = []
        self._fabric = _FakeFabric()


def _legacy_stats(wall, pool_iv, train_iv, pub_iv, rows, publish_wait):
    gen_iv = _merge_intervals(pool_iv)
    pub_m = _merge_intervals(pub_iv)
    return {
        "wall_s": wall,
        "gen_busy_s": sum(e - s for s, e in gen_iv),
        "gen_worker_s": sum(e - s for s, e in pool_iv),
        "train_busy_s": sum(e - s for s, e in train_iv),
        "overlap_s": _interval_overlap(gen_iv, train_iv),
        "gen_idle_s": sum(r["gen_idle_s"] for r in rows),
        "train_idle_s": sum(r["train_idle_s"] for r in rows),
        "publish_s": sum(e - s for s, e in pub_m),
        "publish_overlap_s": _interval_overlap(gen_iv, pub_m),
        "publish_wait_s": sum(publish_wait),
    }


def test_runstats_bit_compatible_with_legacy_formula():
    """The incremental ``_RunStats`` source reproduces the legacy
    re-merge-everything stats dict exactly -- keys and values -- fed the
    same interval streams, including a stale-prefix fabric history
    (pub0) and pre-existing history rows (first)."""
    rng = random.Random(42)
    ctl = _FakeCtl()
    pool = _FakePool()
    train_iv, publish_wait = [], []
    # pre-run leftovers that must be excluded
    ctl._fabric.intervals = [(0.0, 1.0)]
    ctl.history = [{"gen_idle_s": 99.0, "train_idle_s": 99.0}]
    src = _RunStats(ctl, pool, train_iv, publish_wait,
                    first=1, wall0=time.monotonic(),
                    pub0=len(ctl._fabric.intervals))
    t = 10.0
    for step in range(30):
        # overlapping worker intervals (two workers), disjoint
        # consumer/publisher intervals -- the real feeds' shapes
        a = t + rng.uniform(0, 0.5)
        pool.intervals.append((a, a + rng.uniform(0.1, 1.0)))
        b = t + rng.uniform(0, 0.5)
        pool.intervals.append((b, b + rng.uniform(0.1, 1.0)))
        train_iv.append((t + 1.0, t + 1.0 + rng.uniform(0.1, 0.4)))
        ctl._fabric.intervals.append((t + 1.5, t + 1.5 + 0.1))
        publish_wait.append(rng.uniform(0, 0.01))
        ctl.history.append({"gen_idle_s": rng.uniform(0, 0.2),
                            "train_idle_s": rng.uniform(0, 0.1)})
        t += 2.0
        if step % 7 == 0:
            live = src.compute()             # mid-run polls hit the cache
            assert live["wall_s"] > 0.0
    src.finish(wall=123.0)
    got = src.compute()
    want = _legacy_stats(123.0, pool.intervals, train_iv,
                         ctl._fabric.intervals[1:], ctl.history[1:],
                         publish_wait)
    assert list(got) == list(want)           # exact key set and order
    for k in want:
        assert got[k] == pytest.approx(want[k], abs=1e-9), k
    # cached: a second poll with no new data is the same dict content
    assert src.compute() == got


def test_runstats_cache_invalidates_on_new_rows():
    ctl = _FakeCtl()
    pool = _FakePool()
    train_iv, publish_wait = [], []
    src = _RunStats(ctl, pool, train_iv, publish_wait,
                    first=0, wall0=time.monotonic(), pub0=0)
    assert src.compute()["gen_busy_s"] == 0.0
    pool.intervals.append((1.0, 2.0))
    pool.intervals.append((1.5, 3.0))
    assert src.compute()["gen_busy_s"] == pytest.approx(2.0)
    assert src.compute()["gen_worker_s"] == pytest.approx(2.5)
    ctl.history.append({"gen_idle_s": 0.25, "train_idle_s": 0.5})
    got = src.compute()
    assert got["gen_idle_s"] == 0.25 and got["train_idle_s"] == 0.5


def test_controller_stats_setter_compat():
    """Code (and checkpoints) that assign ``ctl.stats = {...}`` keep
    working: the setter detaches any live source."""
    ctl = build_supervised(n_gens=1, max_steps=2, transport="inproc",
                           supervise=False)
    ctl.stats = {"wall_s": 1.0}
    assert ctl.stats == {"wall_s": 1.0}
