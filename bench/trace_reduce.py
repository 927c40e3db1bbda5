"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: per device, the union of the intervals in which an operation ran,
the time of each operation and each compiled program, and the idle gaps
named by the benchmark's host spans (``bench:*`` annotations) that were
open in them.

Only ``jax.profiler.ProfileData`` is used to read the file.  On a TPU the
device's timeline sits about a millisecond off the host's (the recorded
trace under ``traces/`` shows it), which names gaps of many
milliseconds correctly and short ones only roughly.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench:"
_CONTAINERS = ("%while", "%conditional", "%call")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _merge(ivs: List[Tuple[float, float]]):
    out: List[List[float]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:")
            and any(line.name == OPS_LINE for line in p.lines)]


class Reduced:
    """Per-device op totals, program totals and busy intervals (ns), plus
    the benchmark's host spans."""

    def __init__(self):
        self.ops: Dict[str, collections.Counter] = {}
        self.op_calls: Dict[str, collections.Counter] = {}
        self.modules: Dict[str, collections.Counter] = {}
        self.module_calls: Dict[str, collections.Counter] = {}
        self.busy: Dict[str, List[List[float]]] = {}
        self.spans: List[Tuple[str, float, float]] = []

    @property
    def devices(self):
        return sorted(self.busy)

    def busy_s(self, device: str) -> float:
        return sum(e - s for s, e in self.busy[device]) * 1e-9

    def op_seconds(self, match, devices=None) -> Dict[str, float]:
        """Seconds per device of the ops whose name ``match`` accepts."""
        return {d: sum(v for k, v in self.ops[d].items() if match(k)) * 1e-9
                for d in (devices or self.devices)}

    def op_calls_matching(self, match, device) -> int:
        return sum(v for k, v in self.op_calls[device].items() if match(k))

    def module_seconds(self, match, device) -> Tuple[float, int]:
        s = sum(v for k, v in self.modules[device].items() if match(k))
        n = sum(v for k, v in self.module_calls[device].items() if match(k))
        return s * 1e-9, n

    def top_ops(self, n: int = 10, devices=None):
        """The operations that took most device time, per chip, named
        short; loops and calls, whose time their bodies' operations
        already count, are left out."""
        devices = devices or self.devices
        tot: collections.Counter = collections.Counter()
        for d in devices:
            tot.update({k: v for k, v in self.ops[d].items()
                        if not k.startswith(_CONTAINERS)})
        return [[short_name(k), v * 1e-9 / len(devices)]
                for k, v in tot.most_common(n)]

    def idle_gaps(self, n: int = 10, devices=None):
        """The longest gaps between device operations, each named by the
        host span that covers most of it."""
        gaps = []
        for d in devices or self.devices:
            iv = self.busy[d]
            for (_, e0), (s1, _) in zip(iv, iv[1:]):
                gaps.append((s1 - e0, e0, s1))
        gaps.sort(reverse=True)
        out = []
        for dur, s, e in gaps[:n]:
            best, cover = "no bench span", 0.0
            for name, hs, he in self.spans:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = name, c
            out.append([best, dur * 1e-9])
        return out


def short_name(op: str) -> str:
    """``%fusion.189 = f32[16,49152]{...} fusion(...)`` -> ``fusion.189
    f32[16,49152]``; a Pallas call gets its kernel's name in front."""
    from bench import kernels
    head, _, rest = op.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0][:60]
    name = f"{head.lstrip('%')} {shape}".strip()
    for kernel in kernels.SIGNATURES:
        if kernels.matcher(kernel)(op):
            return f"{kernel} {name}"
    return name


def reduce_profile(pd) -> Reduced:
    r = Reduced()
    for plane in _device_planes(pd):
        ops = r.ops[plane.name] = collections.Counter()
        calls = r.op_calls[plane.name] = collections.Counter()
        mods = r.modules[plane.name] = collections.Counter()
        mcalls = r.module_calls[plane.name] = collections.Counter()
        ivs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s, d = ev.start_ns, ev.duration_ns
                    ops[ev.name] += d
                    calls[ev.name] += 1
                    ivs.append((s, s + d))
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    mods[ev.name] += ev.duration_ns
                    mcalls[ev.name] += 1
        r.busy[plane.name] = _merge(ivs)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    r.spans.append((ev.name[len(SPAN_PREFIX):], ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
    return r


def load(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)))


def load_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


def device_key(device_id: int) -> str:
    return f"/device:TPU:{device_id}"


def for_devices(r: Reduced, device_ids) -> Optional[List[str]]:
    keys = [device_key(i) for i in device_ids]
    return [k for k in keys if k in r.busy] or None
