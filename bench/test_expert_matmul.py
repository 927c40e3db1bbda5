"""The expert-matmul roofline's reader: on a synthetic trace, the window's
passes split by side from the kernels' calls, each side's work from the
program's counters, and a share over 100 % refused; on the operations of
a recorded v5e trace of the cell, the operations that stage the
kernels' weights, found by producer."""
import collections
import json
import os
from types import SimpleNamespace

import pytest

from bench import expert_matmul as em
from bench import spec as bspec
from bench.trace_reduce import Reduced

DEV = "/device:TPU:0"
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
GMM = ("%custom-call.1 = f32[184,896]{1,0} custom-call(s32[23]{0} %a, "
       "s32[1]{0} %b, f32[184,2304]{1,0} %x, "
       "f32[8,2304,896]{2,1,0:T(8,128)S(1)} %dynamic-slice_bitcast_fusion.16"
       '), custom_call_target="tpu_custom_call"')
TGMM = ("%custom-call.2 = f32[8,2304,896]{2,1,0} custom-call(s32[137]{0} %a, "
        "s32[1]{0} %b, f32[35072,2304]{1,0} %x, f32[35072,896]{1,0} %g), "
        'custom_call_target="tpu_custom_call"')
COPY = ("%dynamic-slice_bitcast_fusion.16 = f32[8,2304,896]{2,1,0:T(8,128)S(1)}"
        " fusion(%get-tuple-element.2755, %get-tuple-element.2711), "
        "kind=kLoop")
# a held-expert weight's shape that feeds no gmm: the weight gradient
# made ready for the optimizer, an update over one layer's weights
CAST = ("%convert_fusion.3 = f32[8,2304,896]{2,1,0:T(8,128)} fusion("
        "f32[8,2304,896]{2,1,0:T(8,128)} %custom-call.2), kind=kLoop")
ADAM = ("%fusion.77 = f32[8,2304,896]{2,1,0:T(8,128)S(1)} fusion("
        "f32[8,2304,896]{2,1,0:T(8,128)} %p.1, f32[8,2304,896]{2,1,0:T(8,128)}"
        " %convert_fusion.3), kind=kLoop")
OTHER = ("%custom-call.3 = f32[16,4,8,128]{3,2,1,0} custom-call(s32[16,34]{1,0}"
         " %t, s32[16]{0} %p, f32[16,4,8,128]{3,2,1,0} %q, f32[529,16,512]"
         "{2,1,0} %k, f32[529,16,512]{2,1,0} %v), "
         'custom_call_target="tpu_custom_call"')


def _ctx(calls, seconds, remat=False):
    r = Reduced()
    r.ops[DEV] = collections.Counter({k: int(v * 1e9)
                                      for k, v in seconds.items()})
    r.op_calls[DEV] = collections.Counter(calls)
    r.busy[DEV] = []
    spec = bspec.load_config("mellum2-12b-a2.5b.ep8-l4")
    run = SimpleNamespace(cfg=SimpleNamespace(remat_layers=remat))
    return SimpleNamespace(trace=r, peak=PEAK, generator_devices=[DEV],
                           spec=spec, run=run)


@pytest.fixture
def counters(monkeypatch):
    from repro.obs import metrics as obs_metrics
    reg = obs_metrics.MetricsRegistry()
    monkeypatch.setattr(obs_metrics, "_registry", reg)
    return reg


def _charge(reg, name, v):
    reg.counter(name).inc(v)


def test_signatures_tell_the_kernels_apart():
    assert em.matcher("gmm")(GMM) and not em.matcher("gmm")(TGMM)
    assert em.matcher("tgmm")(TGMM) and not em.matcher("tgmm")(GMM)
    assert not em.is_grouped_matmul(OTHER)
    # the copy that stages a gmm call's weights counts too, found by
    # producer; ops of a weight's shape that feed no gmm do not
    ops = [GMM, TGMM, COPY, CAST, ADAM, OTHER]
    match = em.product_matcher(ops)
    assert [match(op) for op in ops] == [True, True, True, False, False,
                                         False]
    assert not em.is_grouped_matmul(COPY)
    # a producer is told by its name and its result type together
    moved = COPY.replace("S(1)}", "}", 1)
    assert em.staging_ops([GMM, moved]) == set()


def _recorded():
    path = os.path.join(os.path.dirname(__file__), "traces", "v5e_mellum2",
                        "expert_ops.json")
    with open(path) as f:
        return json.load(f)


def _short(op):
    return op.split(" = ", 1)[0] + " " + op.split(" = ", 1)[1].split(" ")[0]


def test_staging_ops_on_a_recorded_v5e_trace():
    """The producers of the gmm calls' weights in a traced window of
    ``mellum2.decode-long`` on a TPU v5e: each layer's weight slice of
    the stacked layers, copies into fast memory with their asynchronous
    starts, and a concatenation of four async slices with those slices.
    Left out: the stacks the slices are taken from, and a concatenation
    of a weight's slices that feeds an update of the stack, not a gmm."""
    rec = _recorded()
    ops = [op for op, _, _ in rec["ops"]]
    staged = {_short(op) for op in em.staging_ops(ops)}
    assert len(staged) == 38
    f32 = "f32[8,2304,896]{2,1,0:T(8,128)S(1)}"
    f32t = "f32[8,896,2304]{2,1,0:T(8,128)S(1)}"
    for want in (f"%dynamic-slice_bitcast_fusion.15 {f32}",
                 f"%dynamic-slice_bitcast_fusion.17 {f32t}",
                 f"%slice_bitcast_fusion.6 {f32}",
                 "%slice_bitcast_fusion.8 f32[8,896,2304]{2,1,0:T(8,128)}",
                 f"%copy-done.21 {f32t}",
                 f"%custom-call.125 {f32t}",
                 "%dynamic-slice_bitcast_fusion.36 "
                 "f32[8,896,2304]{2,1,0:T(8,128)}"):
        assert want in staged, want
    assert sum(s.startswith("%copy-start.") for s in staged) == 4
    assert sum(s.startswith("%slice-start.") for s in staged) == 4
    for left in ("%custom-call.126 ", "%slice-start.46 ", "%slice.609 ",
                 "%slice.610 ", "%slice.612 ", "%copy.163 "):
        assert not any(s.startswith(left) for s in staged), left
    assert not any(em.is_grouped_matmul(op) for op in em.staging_ops(ops))
    ns = dict((op, t) for op, t, _ in rec["ops"])
    assert sum(ns[op] for op in em.staging_ops(ops)) * 1e-9 == \
        pytest.approx(6.094, abs=1e-3)


def test_roofline_on_a_recorded_v5e_trace(counters):
    """The metric read from the recorded window and the run's counters:
    a share, under 100 %, with the staging copies in the time."""
    rec = _recorded()
    r = Reduced()
    r.ops[DEV] = collections.Counter({op: t for op, t, _ in rec["ops"]})
    r.op_calls[DEV] = collections.Counter({op: n for op, _, n in rec["ops"]})
    r.busy[DEV] = []
    for name, v in rec["counters"].items():
        if name != "moe.experts_held":
            _charge(counters, name, v)
    spec = bspec.load_config("mellum2-12b-a2.5b.ep8-l4")
    run = SimpleNamespace(cfg=SimpleNamespace(remat_layers=rec["remat_layers"]))
    ctx = SimpleNamespace(trace=r, peak=PEAK, generator_devices=[DEV],
                          spec=spec, run=run)
    pct = bspec.metric_reader("expert_matmul_roofline")(ctx)
    assert 0 < pct < 100
    assert pct == pytest.approx(27.6, abs=0.1)


@pytest.mark.parametrize("remat", [False, True])
def test_roofline_splits_passes_by_side(counters, remat):
    reader = bspec.metric_reader("expert_matmul_roofline")
    # run totals: 1000 generator passes of 16 pairs on 7 experts, 10
    # trainer passes of 4104 pairs on 8 experts
    for name, gen, trn in (("moe.products", 1000, 10),
                           ("moe.pairs_held", 16000, 41040),
                           ("moe.experts_hit", 7000, 80)):
        _charge(counters, name, gen + trn)
        _charge(counters, name + ".trainer", trn)
    # the window: 100 generator passes, 2 trainer passes
    per_pass = 9 if remat else 6
    calls = {GMM: 3 * 100 + per_pass * 2, TGMM: 3 * 2}
    D, F = 2304, 896
    gen = em.least_seconds(PEAK, pairs=16, hits=7, d_model=D, d_expert=F)
    trn = em.least_seconds(PEAK, pairs=4104, hits=8, d_model=D, d_expert=F,
                           passes=3)
    least = 100 * gen + 2 * trn
    pct = reader(_ctx(calls, {GMM: least, TGMM: 0.0, COPY: least,
                              OTHER: 5 * least}, remat))
    assert pct == pytest.approx(50.0)
    # a decode pass is bound by the experts' bytes, a trainer pass near
    # the balance of FLOPs and bytes
    assert gen == pytest.approx((12 * D * F * 7 + 4 * 3 * (D + F) * 16)
                                / PEAK["hbm_bytes_per_s"])
    with pytest.raises(ValueError, match="> 100%"):
        reader(_ctx(calls, {GMM: 0.9 * least, TGMM: 0.0}, remat))


def test_roofline_reads_nothing_without_counters(counters):
    reader = bspec.metric_reader("expert_matmul_roofline")
    assert reader(_ctx({GMM: 3}, {GMM: 1e-3})) is None
