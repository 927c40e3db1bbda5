"""The reduction from a profiler trace to the per-layer metrics' inputs,
held to a small trace recorded on a TPU v5e (``record_trace.py``): the
kernels' calls found by their signatures, the idle gap named by the host
span it fell in, busy time inside the traced span."""
import json
import os

import pytest

from bench import kernels, trace_reduce

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces",
                    "v5e_kernels")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.load_file(os.path.join(HERE, "trace.xplane.pb"))


def test_one_tpu_device(reduced, recorded):
    assert reduced.devices == ["/device:TPU:0"]
    assert recorded["device_kind"] == "TPU v5 lite"


@pytest.mark.parametrize("kernel,per_call", [("paged_attention", 1),
                                             ("flash_attention", 1),
                                             ("fused_logprob", 2)])
def test_kernel_calls_found_by_signature(reduced, recorded, kernel,
                                         per_call):
    d = reduced.devices[0]
    n = reduced.op_calls_matching(kernels.matcher(kernel), d)
    assert n == per_call * recorded["calls"][kernel]
    assert reduced.op_seconds(kernels.matcher(kernel))[d] > 0


def test_kernels_do_not_match_each_other(reduced):
    d = reduced.devices[0]
    names = list(reduced.ops[d])
    for a in kernels.SIGNATURES:
        for b in kernels.SIGNATURES:
            if a != b:
                both = [n for n in names if kernels.matcher(a)(n)
                        and kernels.matcher(b)(n)]
                assert not both, (a, b)


def test_idle_gap_is_named_by_its_span(reduced, recorded):
    name, seconds = reduced.idle_gaps(1)[0]
    assert name == recorded["idle_span"]
    assert seconds >= recorded["sleep_s"]


def test_busy_time_fits_in_the_spans(reduced, recorded):
    d = reduced.devices[0]
    spans = {n: (s, e) for n, s, e in reduced.spans}
    assert set(spans) == set(recorded["spans"])
    lo = min(s for s, _ in spans.values())
    hi = max(e for _, e in spans.values())
    busy = reduced.busy_s(d)
    assert 0 < busy < (hi - lo) * 1e-9 - recorded["sleep_s"]
    # the device's timeline sits about a millisecond off the host's here
    slack = 2e6
    assert reduced.busy[d][0][0] >= lo - slack
    assert reduced.busy[d][-1][1] <= hi + slack
    top = reduced.top_ops(3)
    assert top and all(s > 0 for _, s in top)
