"""The reduction that charges device-idle time to the program's own spans,
held to constructed traces whose answer is known by hand: nesting, spans
named ``-wait`` passed over, two threads busy at once, idle time under
only ``-wait`` spans or none, and the clock shift from dispatch spans.
Then the three numbers built on it, with and without a trace, and a
whole traced run with the tracer on at the smoke size."""
from types import SimpleNamespace as NS

import pytest

from bench import program_spans as ps

DEV = "/device:TPU:0"
NS_ = 1e-9


def _ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def _profile(threads, modules=()):
    """A stand-in for ``ProfileData``: one host plane with a line per
    thread of ``(name, start, end)`` spans, and a device plane whose
    ``XLA Modules`` line holds ``modules``."""
    lines = [NS(name="python", events=[_ev(ps.PREFIX + n, s, e)
                                       for n, s, e in spans]
                + [_ev("PjitFunction(step)", 0, 1)])
             for spans in threads]
    device = NS(name=DEV, lines=[NS(name=ps.MODULES_LINE, events=[
        _ev(n, s, e) for n, s, e in modules])])
    return NS(planes=[NS(name="/host:CPU", lines=lines), device])


def _charge(threads, busy, modules=()):
    return ps.from_profile(_profile(threads, modules), {DEV: busy})


def _ns(counter):
    return {k: round(v / NS_, 6) for k, v in counter.items()}


def test_nested_spans_charge_the_innermost():
    # gaps 10-30 and 40-90; inner is open 20-50 inside outer 0-100
    r = _charge([[("a.outer", 0, 100), ("a.inner", 20, 50)]],
                [[0, 10], [30, 40], [90, 200]])
    assert _ns(r.charged[DEV]) == {"a.outer": 50, "a.inner": 20}
    assert r.idle_s[DEV] == pytest.approx(70 * NS_)
    assert r.unattributed_s[DEV] == r.wait_only_s[DEV] == 0


def test_wait_spans_are_passed_over_and_counted_apart():
    # the wait inside outer charges outer; it is also listed as a wait
    r = _charge([[("a.outer", 0, 100), ("a.readback-wait", 20, 50)]],
                [[0, 10], [30, 40], [90, 200]])
    assert _ns(r.charged[DEV]) == {"a.outer": 70}
    assert _ns(r.waits[DEV]) == {"a.outer>a.readback-wait": 20}


def test_two_busy_threads_are_both_charged():
    r = _charge([[("gen.round", 0, 100)], [("trainer.step", 20, 60)],
                 [("controller.harvest-wait", 0, 100)]],
                [[0, 10], [30, 40], [90, 200]])
    # 10-30: gen alone 10-20, both 20-30; 40-90: both 40-60, gen 60-90
    assert _ns(r.charged[DEV]) == {"gen.round": 70, "trainer.step": 30}
    assert _ns(r.waits[DEV]) == {"controller.harvest-wait": 70}
    assert r.unattributed_s[DEV] == r.wait_only_s[DEV] == 0
    assert r.charged_s(("gen.",), [DEV]) == pytest.approx(70 * NS_)


def test_idle_under_only_waits_or_no_span_is_apart():
    # gaps 10-30 (wait only), 40-60 (wait to 50, then none), 70-90 (none)
    r = _charge([[("controller.harvest-wait", 0, 50)]],
                [[0, 10], [30, 40], [60, 70], [90, 100]])
    assert not r.charged[DEV]
    assert r.wait_only_s[DEV] == pytest.approx(30 * NS_)
    assert r.unattributed_s[DEV] == pytest.approx(30 * NS_)
    s = r.summary([DEV])
    assert s["unattributed_share"] == pytest.approx(0.5)
    assert s["wait_only_share"] == pytest.approx(0.5)


def test_a_child_outlasting_its_parent_is_cut_at_the_parent():
    r = _charge([[("a.outer", 0, 50), ("a.inner", 20, 60)]],
                [[0, 10], [80, 90]])
    # gap 10-80: outer 10-20, inner 20-50, none 50-80
    assert _ns(r.charged[DEV]) == {"a.outer": 10, "a.inner": 30}
    assert r.unattributed_s[DEV] == pytest.approx(30 * NS_)


def test_device_clock_behind_dispatch_is_shifted():
    # the second decode program "starts" 5 ns before its dispatch span;
    # the first program ran before the trace's first span and is skipped
    modules = [("jit_rollout_rows_chunk.7", 0, 50),
               ("jit_rollout_rows_chunk.7", 105, 150),
               ("jit_rollout_rows_chunk.7", 295, 350)]
    threads = [[("engine.decode-round", 100, 110),
                ("engine.harvest", 150, 200),
                ("engine.decode-round", 300, 310)]]
    busy = [[0, 50], [105, 150], [295, 350]]
    r = _charge(threads, busy, modules)
    assert r.leads == {"engine.decode-round": -5.0}
    assert r.shift_ns == 5.0
    # busy moves 5 ns later, gaps to 55-110 and 155-300: the first
    # dispatch span holds 100-110 of one (5 unshifted), harvest 155-200
    # of the other (50 unshifted)
    assert _ns(r.charged[DEV]) == {"engine.decode-round": 10,
                                   "engine.harvest": 45}
    assert r.summary([DEV])["shift_ms"] == pytest.approx(5e-6)


def test_device_clock_ahead_is_not_shifted():
    modules = [("jit_train_step.3", 120, 200)]
    r = _charge([[("trainer.dispatch", 100, 110)]], [[120, 200]], modules)
    assert r.leads == {"trainer.dispatch": 20.0} and r.shift_ns == 0.0


def test_no_program_spans_charges_nothing():
    r = _charge([], [[0, 10], [30, 40]])
    assert not r.spans and not r.charged[DEV]
    assert r.unattributed_s[DEV] == pytest.approx(20 * NS_)
    assert r.shift_ns == 0.0 and not r.has("engine.")


# ------------------------------------------------------------ the readers --

READERS = tuple(ps.NUMBERS)


def _run(registry_open=None, registry_close=None, steps=4):
    return NS(probe=NS(registry_open=registry_open,
                       registry_close=registry_close),
              steps=list(range(steps)))


def _ctx(program, run):
    return NS(program=program, run=run, generator_devices=[DEV],
              trainer_devices=[DEV])


def _counters(rounds, live, slots=16):
    return {"engine.rounds": {"type": "counter", "value": rounds},
            "engine.live_row_rounds": {"type": "counter", "value": live},
            "engine.slots": {"type": "gauge", "value": slots}}


@pytest.mark.parametrize("name", READERS)
def test_readers_give_none_without_a_trace(name):
    _, read = ps.NUMBERS[name]
    assert read(_ctx(None, _run())) is None


@pytest.mark.parametrize("name", READERS[:2])
def test_span_readers_give_none_where_the_program_has_no_spans(name):
    # a program without the spans (an older checkout) is read, not raised on
    _, read = ps.NUMBERS[name]
    ctx = _ctx(_charge([], [[0, 10], [30, 40]]),
               _run(_counters(0, 0), _counters(10, 120)))
    assert read(ctx) is None


def test_readers_on_a_stub_context():
    # 1 ms of idle in engine spans, 2 ms in the trainer's, over 10 rounds
    # and 4 steps; 120 live rows over 10 rounds of 16 slots
    ms = 1e6
    threads = [[("genpool.tick", 0, 10 * ms),
                ("engine.harvest", 1 * ms, 3 * ms)],
               [("trainer.step", 4 * ms, 9 * ms),
                ("trainer.readback-wait", 5 * ms, 6 * ms)]]
    busy = [[0, 2 * ms], [3 * ms, 4 * ms], [6 * ms, 10 * ms]]
    ctx = _ctx(_charge(threads, busy),
               _run(_counters(5, 40), _counters(15, 160)))
    got = {n: read(ctx) for n, (_, read) in ps.NUMBERS.items()}
    # gaps 2-3 ms (harvest) and 4-6 ms (tick and trainer.step)
    assert got["engine_round_gap_ms"] == pytest.approx((1 + 2) / 10)
    assert got["train_step_gap_ms"] == pytest.approx(2 / 4)
    assert got["slot_occupancy"] == pytest.approx(100 * 120 / (10 * 16))


# ------------------------------------------------- a whole traced run --

def test_traced_run_with_the_tracer_on_at_smoke_size():
    # the CPU trace has no device plane: the span numbers give None, the
    # counters still give the occupancy; the harness is left as it was
    import time

    from bench import harness, program_idle, smoke
    from repro.obs import trace as obs_trace

    base = harness.Probe
    cell = smoke.smoke_cell("sc2-3b.decode-long")
    res = program_idle.run_traced(cell, 5, 0.5, t_start=time.perf_counter(),
                                  require_chip=False)
    assert harness.Probe is base and not obs_trace.enabled()
    assert res["correct"], res["compared"]
    occ = res["metrics"]["slot_occupancy"]
    assert occ["unit"] == "%" and 0 < occ["value"] <= 100
    assert "engine_round_gap_ms" not in res["metrics"]
