#!/usr/bin/env python3
"""Record the small profiler trace that ``test_trace_reduce.py`` reduces.

    python3 bench/record_trace.py --out bench/traces/v5e_kernels

On one chip, inside ``bench:`` host spans: the paged decode-attention
kernel, the fused log-prob kernel (forward and backward) and the flash
attention kernel at StarCoder2-3B widths, a fixed number of calls each,
and a 50 ms sleep with nothing on the device.  It writes the trace and a
JSON of what was done (calls per kernel, the spans) beside it, so the
test can hold the reduction to known counts.
"""
import argparse
import glob
import json
import os
import shutil
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO_ROOT, os.path.join(REPO_ROOT, "src")]

CALLS = {"paged_attention": 3, "fused_logprob": 2, "flash_attention": 2}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    opts = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("needs the chip")
    H, K, hd, V, P = 24, 2, 128, 49152, 16
    rows, mb = 8, 33
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (rows, H, hd))
    arena = jax.random.normal(key, (rows * mb + 1, P, K, hd))
    table = jnp.arange(rows * (mb + 1), dtype=jnp.int32).reshape(rows, mb + 1) \
        % (rows * mb + 1)
    pos = jnp.full((rows,), 400, jnp.int32)
    paged = jax.jit(lambda q, a, t, p: dispatch.paged_attention(q, a, a, t, p))
    logits = jax.random.normal(key, (rows * 64, V))
    toks = jnp.zeros((rows * 64,), jnp.int32)
    lp = jax.jit(jax.value_and_grad(
        lambda x, t: jnp.sum(dispatch.token_logprob(x, t))))
    qf = jax.random.normal(key, (1, 512, H, hd))
    kf = jax.random.normal(key, (1, 512, K, hd))
    flash = jax.jit(lambda q, k: dispatch.attention(q, k, k))
    # compile outside the trace
    jax.block_until_ready((paged(q, arena, table, pos), lp(logits, toks),
                           flash(qf, kf)))

    tmp = os.path.join(os.environ.get("TMPDIR", "/tmp"), "bench_record")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench:paged"):
        for _ in range(CALLS["paged_attention"]):
            jax.block_until_ready(paged(q, arena, table, pos))
    with jax.profiler.TraceAnnotation("bench:idle-wait"):
        time.sleep(0.05)
    with jax.profiler.TraceAnnotation("bench:logprob"):
        for _ in range(CALLS["fused_logprob"]):
            jax.block_until_ready(lp(logits, toks))
    with jax.profiler.TraceAnnotation("bench:flash"):
        for _ in range(CALLS["flash_attention"]):
            jax.block_until_ready(flash(qf, kf))
    jax.profiler.stop_trace()

    os.makedirs(opts.out, exist_ok=True)
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(opts.out, "trace.xplane.pb"))
    with open(os.path.join(opts.out, "recorded.json"), "w") as f:
        json.dump({"device_kind": dev.device_kind, "calls": CALLS,
                   "spans": ["paged", "idle-wait", "logprob", "flash"],
                   "idle_span": "idle-wait", "sleep_s": 0.05}, f, indent=1)
    from bench import trace_reduce
    r = trace_reduce.load_file(os.path.join(opts.out, "trace.xplane.pb"))
    for d in r.devices:
        print(d, "busy_s", r.busy_s(d))
        for name, n in r.op_calls[d].most_common(40):
            print(f"  op {name!r} calls {n} s {r.ops[d][name] * 1e-9:.6f}")
        for name, n in r.module_calls[d].most_common(10):
            print(f"  module {name!r} calls {n}")
        print("  gaps", r.idle_gaps(5, [d]))
    print("spans", r.spans)
    shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
