"""The program's own spans in a profiler trace, and the device's idle time
charged to the host phase that was open.

While ``repro.obs.trace`` is enabled, each live span opens a profiler
annotation ``repro:{cat}.{name}`` on its thread, so the spans land in the
same ``.xplane.pb`` as the device's operations.  This module rebuilds them
per host line (one line per thread) with their nesting, then charges each
idle interval between operations on each chip to the innermost span open
on each thread whose name does not end in ``-wait`` (a ``-wait`` span
blocks on another thread or on the chip, so it is not what kept the chip
idle).  One interval counts for every thread that was busy in it; idle
time under no span, or only under ``-wait`` spans, is counted apart.

Clock check: a device's program cannot start before the host span that
dispatched it opened.  The k-th ``engine.decode-round`` and
``trainer.dispatch`` span is matched with the k-th execution of the
program it launches; if the smallest lead of a program over its span is
negative, the device timeline is shifted by it before charging.

``bench/program_idle.py`` runs a cell with the tracer on and prints what
this module finds, with the three numbers of ``NUMBERS``.
"""
from __future__ import annotations

import collections
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

PREFIX = "repro:"
WAIT_SUFFIX = "-wait"
MODULES_LINE = "XLA Modules"
#: dispatching span -> name prefix of the device program it launches
DISPATCHES = {"engine.decode-round": "jit_rollout_rows_chunk",
              "trainer.dispatch": "jit_train_step"}

Span = Tuple[float, float, str]          # start ns, end ns, "cat.name"


def host_spans(pd) -> Dict[Tuple[str, int], List[Span]]:
    """The ``repro:`` annotations of every host line, keyed by (plane,
    line index), each line's spans sorted by start."""
    out: Dict[Tuple[str, int], List[Span]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            spans = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                      ev.name[len(PREFIX):])
                     for ev in line.events if ev.name.startswith(PREFIX)]
            if spans:
                out[(plane.name, i)] = sorted(spans, key=_nest_order)
    return out


def module_runs(pd) -> Dict[str, List[Tuple[float, float, str]]]:
    """Per device plane, each program execution's (start, end, name)."""
    out: Dict[str, List[Tuple[float, float, str]]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out[plane.name] = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events)
    return out


def _nest_order(span: Span):
    return span[0], -span[1]


def segments(spans: List[Span]):
    """One thread's timeline as ``(t0, t1, charged, innermost)`` pieces:
    in each piece the same spans are open; ``charged`` is the innermost
    one not named ``*-wait`` (None when only ``-wait`` spans are open)
    and ``innermost`` the innermost of all.  A child that outlasts its
    parent by clock rounding is cut at the parent's end."""
    out = []
    stack: List[Tuple[float, str]] = []     # (end, name), outermost first
    t = 0.0

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end = stack[-1][0]
            _piece(out, t, end, stack)
            stack.pop()
            t = end

    for s, e, name in sorted(spans, key=_nest_order):
        close_until(s)
        if stack:
            _piece(out, t, s, stack)
            e = min(e, stack[-1][0])
        t = s
        stack.append((e, name))
    close_until(float("inf"))
    return out


def _piece(out, t0, t1, stack):
    if t1 <= t0:
        return
    charged = next((n for _, n in reversed(stack)
                    if not n.endswith(WAIT_SUFFIX)), None)
    out.append((t0, t1, charged, stack[-1][1]))


def gaps(busy) -> Tuple[np.ndarray, np.ndarray]:
    """Idle intervals between a device's merged busy intervals."""
    b = np.asarray(busy, np.float64).reshape(-1, 2)
    return b[:-1, 1].copy(), b[1:, 0].copy()


class IdleClock:
    """Idle time of one device before any instant, from its gaps."""

    def __init__(self, starts: np.ndarray, ends: np.ndarray):
        if not len(starts):             # one busy interval: no gap
            starts = ends = np.zeros(1)
        self.starts, self.ends = starts, ends
        self.cum = np.concatenate([[0.0], np.cumsum(ends - starts)])
        self.total = float(self.cum[-1])

    def before(self, t) -> np.ndarray:
        t = np.asarray(t, np.float64)
        k = np.searchsorted(self.starts, t, side="right")   # gaps begun
        last = np.maximum(k - 1, 0)
        partial = np.where(k > 0, np.minimum(t, self.ends[last])
                           - self.starts[last], 0.0)
        return self.cum[last] * (k > 0) + np.maximum(partial, 0.0)

    def within(self, t0, t1) -> np.ndarray:
        return self.before(t1) - self.before(t0)


def _union(pieces) -> np.ndarray:
    ivs = sorted((a, b) for a, b in pieces)
    out: List[List[float]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64).reshape(-1, 2)


def dispatch_leads(spans, modules) -> Dict[str, float]:
    """Smallest lead (ns) of each device program over the span that
    dispatched it, per dispatching span name with a match.  Programs that
    ended before the first such span opened were dispatched before the
    trace began and are skipped."""
    leads: Dict[str, float] = {}
    for kind, prefix in DISPATCHES.items():
        opened = sorted(s for line in spans.values() for s, _, n in line
                        if n == kind)
        if not opened:
            continue
        for runs in modules.values():
            starts = [s for s, e, n in runs if n.startswith(prefix)
                      and e > opened[0]]
            pairs = list(zip(opened, starts))
            if pairs:
                lead = min(m - h for h, m in pairs)
                leads[kind] = min(lead, leads.get(kind, lead))
    return leads


class ProgramIdle:
    """The program's spans and each device's idle time charged to them.

    ``charged[d]`` maps span name to idle seconds (every busy thread
    counted); ``waits[d]`` maps a ``-wait`` span that was innermost on a
    thread, as ``charged>wait`` where a span was charged, to the idle
    seconds spent in it; ``wait_only_s[d]`` is idle time with only
    ``-wait`` spans open, ``unattributed_s[d]`` idle time under no span,
    ``idle_s[d]`` all idle time between operations."""

    def __init__(self, spans, modules, busy):
        self.spans = spans
        self.leads = dispatch_leads(spans, modules)
        low = min(self.leads.values(), default=0.0)
        self.shift_ns = -low if low < 0 else 0.0
        pieces = [p for line in spans.values() for p in segments(line)]
        charged_union = _union((a, b) for a, b, c, _ in pieces
                               if c is not None)
        spanned_union = _union((a, b) for a, b, _, _ in pieces)
        self.charged: Dict[str, collections.Counter] = {}
        self.waits: Dict[str, collections.Counter] = {}
        self.idle_s: Dict[str, float] = {}
        self.wait_only_s: Dict[str, float] = {}
        self.unattributed_s: Dict[str, float] = {}
        t0 = np.asarray([p[0] for p in pieces], np.float64)
        t1 = np.asarray([p[1] for p in pieces], np.float64)
        for d, iv in busy.items():
            s, e = gaps(iv)
            clock = IdleClock(s + self.shift_ns, e + self.shift_ns)
            idle = clock.within(t0, t1) if pieces else np.zeros(0)
            charged = self.charged[d] = collections.Counter()
            waits = self.waits[d] = collections.Counter()
            for (_, _, c, inner), x in zip(pieces, idle):
                if not x:
                    continue
                if c is not None:
                    charged[c] += x * 1e-9
                if inner.endswith(WAIT_SUFFIX):
                    waits[inner if c is None else f"{c}>{inner}"] += \
                        x * 1e-9
            in_charged = clock.within(*charged_union.T).sum()
            in_spans = clock.within(*spanned_union.T).sum()
            self.idle_s[d] = clock.total * 1e-9
            self.wait_only_s[d] = float(in_spans - in_charged) * 1e-9
            self.unattributed_s[d] = float(clock.total - in_spans) * 1e-9

    def has(self, prefix: str) -> bool:
        """Whether any span of the trace starts with ``prefix``."""
        return any(n.startswith(prefix) for line in self.spans.values()
                   for _, _, n in line)

    def charged_s(self, prefixes: Iterable[str], devices) -> float:
        """Idle seconds charged to spans named with any of ``prefixes``,
        averaged over ``devices``."""
        prefixes = tuple(prefixes)
        per = [sum(v for k, v in self.charged[d].items()
                   if k.startswith(prefixes)) for d in devices]
        return sum(per) / len(per)

    def summary(self, devices) -> dict:
        """The ``program idle:`` line: per span the charged idle seconds
        averaged over ``devices``, the shares of idle time under only
        ``-wait`` spans and under no span, and the clock shift."""
        n = len(devices)

        def mean(per_device):
            tot: collections.Counter = collections.Counter()
            for d in devices:
                tot.update(per_device[d])
            return {k: v / n for k, v in tot.most_common()}

        idle = sum(self.idle_s[d] for d in devices)
        share = (lambda x: sum(x[d] for d in devices) / idle) if idle \
            else (lambda x: None)
        return {"idle_s": idle / n, "charged_s": mean(self.charged),
                "waits_s": mean(self.waits),
                "wait_only_share": share(self.wait_only_s),
                "unattributed_share": share(self.unattributed_s),
                "shift_ms": self.shift_ns * 1e-6,
                "leads_ms": {k: v * 1e-6 for k, v in self.leads.items()}}


def from_profile(pd, busy) -> ProgramIdle:
    return ProgramIdle(host_spans(pd), module_runs(pd), busy)


def load(trace_dir: str, reduced) -> ProgramIdle:
    """Read the trace under ``trace_dir`` again for its host lines and
    program runs, charging the idle time of ``reduced``'s devices."""
    from jax.profiler import ProfileData

    from bench import trace_reduce
    pd = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    return from_profile(pd, reduced.busy)


def counter_delta(run, name: str) -> Optional[float]:
    """Change of a registry instrument over the window, None where the
    run took no snapshot or the program has no such instrument."""
    p = run.probe
    a = (getattr(p, "registry_open", None) or {}).get(name)
    b = (getattr(p, "registry_close", None) or {}).get(name)
    if a is None or b is None:
        return None
    return b["value"] - a["value"]


def gauge(run, name: str) -> Optional[float]:
    """A registry gauge as the window closed."""
    b = (getattr(run.probe, "registry_close", None) or {}).get(name)
    return None if b is None else b["value"]


# The numbers the spans and counters give.  Each reads a metric context
# (``bench/run.py``'s) that also holds ``program`` (a ``ProgramIdle`` or
# None) and a probe with the registry snapshots of the window's edges,
# and returns None where the run holds nothing for it to read.

def engine_round_gap_ms(ctx) -> Optional[float]:
    """Device-idle time on the generator's chips charged to the pool
    worker's and the engine's own spans (``genpool.*``, ``engine.*``), per
    engine round that dispatched a chunk (the change of ``engine.rounds``
    over the window), in ms."""
    prog = getattr(ctx, "program", None)
    if prog is None or not ctx.generator_devices or not prog.has("engine."):
        return None
    rounds = counter_delta(ctx.run, "engine.rounds")
    if not rounds:
        return None
    idle = prog.charged_s(("genpool.", "engine."), ctx.generator_devices)
    return 1e3 * idle / rounds


def train_step_gap_ms(ctx) -> Optional[float]:
    """Device-idle time on the trainer's chips charged to the train
    step's own spans (``trainer.*``), per train step of the window, in
    ms."""
    prog = getattr(ctx, "program", None)
    if prog is None or not ctx.trainer_devices or not prog.has("trainer."):
        return None
    idle = prog.charged_s(("trainer.",), ctx.trainer_devices)
    return 1e3 * idle / len(ctx.run.steps)


def slot_occupancy(ctx) -> Optional[float]:
    """Rows live at each engine round's dispatch over the engine's slots:
    the change of ``engine.live_row_rounds`` over the change of
    ``engine.rounds`` times the ``engine.slots`` gauge, in percent."""
    rounds = counter_delta(ctx.run, "engine.rounds")
    live = counter_delta(ctx.run, "engine.live_row_rounds")
    slots = gauge(ctx.run, "engine.slots")
    if not rounds or live is None or not slots:
        return None
    return 100.0 * live / (rounds * slots)


#: name -> (unit, reader)
NUMBERS = {"engine_round_gap_ms": ("ms", engine_round_gap_ms),
           "train_step_gap_ms": ("ms", train_step_gap_ms),
           "slot_occupancy": ("%", slot_occupancy)}
