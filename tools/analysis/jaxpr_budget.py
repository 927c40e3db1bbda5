"""JAX hot-path budgets: jaxpr intermediate accounting, retrace
counting, and a dispatch-bypass source lint.

The jaxpr helpers here are the single source of truth shared with
``tests/test_dispatch.py`` and ``tests/test_rollout_retrace.py``.  The
``HOT_PATHS`` registry declares each hot path (trainer loss,
``fused_logprob``/``fused_sample``, ``rollout_chunk``, attention) with
a budget -- the max number of float intermediates at or above the
path's "full materialization" size, or the max number of fresh jit
cache entries -- and ``run()`` fails when a path exceeds its budget
(i.e. someone reintroduced a full-vocab log-softmax or a per-call
retrace).

``lint_sources`` is a static companion: direct ``jax.nn.softmax`` /
``jax.nn.log_softmax`` calls outside ``src/repro/kernels/`` are
reported so full-vocab math can't silently bypass
``kernels/dispatch.py`` (legitimate per-block attention softmaxes are
baseline entries).

``lint_trace_staging`` guards the observability boundary (ISSUE 8):
``repro.obs`` is host-side Python -- a span or metric call staged into
a jitted hot path would either break tracing (python side effects
vanish under jit) or silently re-trace, so any ``repro.obs`` import in
the jit-staged modules (``kernels/``, ``models/``, ``rl/rollout.py``,
``core/aipo.py``) is a finding.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import Callable, List, Optional

from .common import Finding, iter_source_files, relpath


# --------------------------------------------------------- jaxpr helpers --

def float_eqn_sizes(jaxpr) -> List[int]:
    """All float eqn-output sizes in a jaxpr, recursing into sub-jaxprs
    (scan/while/cond/pallas bodies via ``eqn.params``); ``reshape`` is
    excluded (pure aliasing in XLA, never a materialization)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.extend.core import ClosedJaxpr, Jaxpr
    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "reshape":
            for var in eqn.outvars:
                aval = var.aval
                if hasattr(aval, "shape") and jnp.issubdtype(
                        aval.dtype, jnp.floating):
                    sizes.append(int(np.prod(aval.shape)) if aval.shape
                                 else 1)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                if isinstance(sub, ClosedJaxpr):
                    sizes.extend(float_eqn_sizes(sub.jaxpr))
                elif isinstance(sub, Jaxpr):
                    sizes.extend(float_eqn_sizes(sub))
    return sizes


def count_big_intermediates(jaxpr, threshold: int) -> int:
    """Number of float intermediates of size >= ``threshold``."""
    return len([s for s in float_eqn_sizes(jaxpr) if s >= threshold])


def jit_cache_entries(fn) -> int:
    """Compilation-cache entry count of a ``jax.jit``-wrapped function."""
    return fn._cache_size()


# ----------------------------------------------------- hot-path registry --

@dataclass(frozen=True)
class HotPath:
    name: str
    budget: int              # max big intermediates (or retraces) allowed
    check: Callable[[], int] # returns the observed count
    what: str                # what the count measures, for messages


def _logprob_fwd() -> int:
    import jax
    from repro.kernels import dispatch
    T, V, bv = 32, 4096, 512
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, V))
    toks = jax.random.randint(jax.random.PRNGKey(1), (T,), 0, V)
    jx = jax.make_jaxpr(
        lambda l: dispatch.token_logprob(l, toks, block_v=bv))(logits)
    return count_big_intermediates(jx.jaxpr, T * V)


def _logprob_grad() -> int:
    import jax
    from repro.kernels import dispatch
    T, V, bv = 32, 4096, 512
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, V))
    toks = jax.random.randint(jax.random.PRNGKey(1), (T,), 0, V)
    jx = jax.make_jaxpr(jax.grad(
        lambda l: dispatch.token_logprob(l, toks, block_v=bv).sum()))(logits)
    return count_big_intermediates(jx.jaxpr, T * V)


def _sample_fwd() -> int:
    import jax
    from repro.kernels import dispatch
    T, V, bv = 32, 4096, 512
    logits = jax.random.normal(jax.random.PRNGKey(0), (T, V))
    jx = jax.make_jaxpr(
        lambda l: dispatch.sample(l, jax.random.PRNGKey(0), 1.0,
                                  block_v=bv))(logits)
    return count_big_intermediates(jx.jaxpr, T * V)


def _trainer_loss_grad() -> int:
    import jax
    import jax.numpy as jnp
    from repro.core import aipo
    # V must clear REPRO_KERNEL_MIN_VOCAB (4096) so token_logprob takes
    # the streamed route, as it does at the paper's V=256k
    B, T, V = 2, 16, 8192
    logits = jax.random.normal(jax.random.PRNGKey(0), (B, T, V))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, V)
    blp = jax.random.normal(jax.random.PRNGKey(2), (B, T)) - 5.0
    adv = jax.random.normal(jax.random.PRNGKey(3), (B, T))
    mask = jnp.ones((B, T))
    jx = jax.make_jaxpr(jax.grad(
        lambda l: aipo.aipo_loss(l, toks, blp, adv, mask)[0]))(logits)
    return count_big_intermediates(jx.jaxpr, B * T * V)


def _attention_chunked() -> int:
    import jax
    from repro.kernels import dispatch
    # S must clear REPRO_KERNEL_MIN_SEQ (512) so attention takes the
    # chunked/streamed route, and the q-block must actually tile S
    # (with block == S "chunked" degenerates to one dense block)
    B, S, H, KvH, D = 1, 512, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KvH, D))
    v = jax.random.normal(ks[2], (B, S, KvH, D))
    jx = jax.make_jaxpr(
        lambda q_: dispatch.attention(q_, k, v, causal=True,
                                      block_q=128))(q)
    return count_big_intermediates(jx.jaxpr, B * H * S * S)


def _rollout_retrace() -> int:
    """Ragged generate (max_new % chunk != 0) must add exactly one
    rollout_chunk jit entry; returns entries added minus the one legal
    compile, so the budget is 0."""
    import jax
    import jax.numpy as jnp
    from repro.configs.llama_paper import smoke
    from repro.models import init_params
    from repro.rl import rollout
    cfg = smoke().replace(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                          head_dim=16, d_ff=64, vocab=32)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    prompts = jnp.full((2, 5), 5, jnp.int32)
    before = jit_cache_entries(rollout.rollout_chunk)
    rollout.generate(params, cfg, prompts, max_new=10,
                     key=jax.random.PRNGKey(1), temperature=1.0, chunk=4)
    rollout.generate(params, cfg, prompts, max_new=10,
                     key=jax.random.PRNGKey(2), temperature=1.0, chunk=4)
    return jit_cache_entries(rollout.rollout_chunk) - before - 1


def _engine_cfg_state():
    import jax
    import jax.numpy as jnp
    from repro.configs.llama_paper import smoke
    from repro.models import init_params
    from repro.rl import rollout
    # vocab large enough that the R*V threshold clears every KV-cache
    # buffer ([R, Sc, KvH, D] is the legitimate bulk of the stitch) and
    # only logits-sized materializations count
    cfg = smoke().replace(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                          head_dim=16, d_ff=64, vocab=4096)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    pool = rollout.start_row_pool(cfg, 4, 9, 5)
    donor = rollout.start_rollout(params, cfg, jnp.full((1, 5), 5, jnp.int32),
                                  9, cache_len=10)
    return cfg, params, pool, donor


def _engine_admit_retrace() -> int:
    """Slot-refill prefill grafts (``admit_row``) into *different* slots
    must share one compilation -- the slot is traced data, not a static
    argument; returns entries added minus the one legal compile."""
    from repro.rl import rollout
    cfg, params, pool, donor = _engine_cfg_state()
    before = jit_cache_entries(rollout.admit_row)
    pool = rollout.admit_row(pool, donor, 0)
    pool = rollout.admit_row(pool, donor, 3)
    return jit_cache_entries(rollout.admit_row) - before - 1


def _engine_admit_vocab() -> int:
    """The admission graft may materialize exactly one [R, V] float --
    the stitched ``last_logits`` buffer itself; anything beyond that is
    a reintroduced full-vocab intermediate."""
    import jax
    from repro.rl import rollout
    cfg, params, pool, donor = _engine_cfg_state()
    jx = jax.make_jaxpr(
        lambda p, d: rollout.admit_row(p, d, 2))(pool, donor)
    R, V = pool.last_logits.shape
    return count_big_intermediates(jx.jaxpr, R * V)


def _engine_rows_retrace() -> int:
    """Decode rounds over the slot pool (``rollout_rows_chunk``) must
    not retrace round-to-round: occupancy changes are data (done flags,
    per-row cursors), never shapes."""
    import jax
    from repro.rl import rollout
    cfg, params, pool, donor = _engine_cfg_state()
    pool = rollout.admit_row(pool, donor, 0)
    before = jit_cache_entries(rollout.rollout_rows_chunk)
    pool = rollout.rollout_rows_chunk(params, cfg, pool,
                                      jax.random.PRNGKey(1), n_steps=2)
    pool = rollout.admit_row(pool, donor, 1)    # occupancy changed
    rollout.rollout_rows_chunk(params, cfg, pool,
                               jax.random.PRNGKey(2), n_steps=2)
    return jit_cache_entries(rollout.rollout_rows_chunk) - before - 1


def _paged_cfg_state():
    import jax
    import jax.numpy as jnp
    from repro.configs.llama_paper import smoke
    from repro.models import init_params
    from repro.rl import rollout
    cfg = smoke().replace(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                          head_dim=16, d_ff=64, vocab=4096)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    # n_pages well beyond what 4 rows need: a full-arena materialization
    # is then strictly larger than any legitimate per-row gather
    pool = rollout.start_row_pool(cfg, 4, 9, 5, kv_layout="paged",
                                  kv_page_size=5, kv_pages=16)
    return cfg, params, pool


def _paged_admit(cfg, params, pool, slot, pages):
    import jax.numpy as jnp
    from repro.rl import rollout
    prompt = jnp.full((1, 5), 5, jnp.int32)
    trash = pool.cache["segments"][0]["k"].shape[1] - 1
    pages_row = jnp.asarray(list(pages) + [trash], jnp.int32)
    return rollout.admit_row_paged(params, cfg, pool, prompt, pages_row,
                                   slot, n_cached=0)


def _paged_admit_retrace() -> int:
    """Paged admissions into different slots with different page tables
    must share one compilation per (cfg, n_cached): slot and table are
    traced data; returns entries added minus the one legal compile."""
    from repro.rl import rollout
    cfg, params, pool = _paged_cfg_state()
    before = jit_cache_entries(rollout.admit_row_paged)
    pool = _paged_admit(cfg, params, pool, 0, (0, 1))
    pool = _paged_admit(cfg, params, pool, 3, (7, 2))
    return jit_cache_entries(rollout.admit_row_paged) - before - 1


def _paged_rows_retrace() -> int:
    """Paged decode rounds must not retrace as occupancy or page-table
    contents change: both are data, never shapes."""
    import jax
    from repro.rl import rollout
    cfg, params, pool = _paged_cfg_state()
    pool = _paged_admit(cfg, params, pool, 0, (0, 1))
    before = jit_cache_entries(rollout.rollout_rows_chunk)
    pool = rollout.rollout_rows_chunk(params, cfg, pool,
                                      jax.random.PRNGKey(1), n_steps=2)
    pool = _paged_admit(cfg, params, pool, 2, (5, 3))   # occupancy+tables
    rollout.rollout_rows_chunk(params, cfg, pool,
                               jax.random.PRNGKey(2), n_steps=2)
    return jit_cache_entries(rollout.rollout_rows_chunk) - before - 1


def _paged_attn_gather() -> int:
    """The paged-attention jnp route gathers per-row pages ([B, mb*P]
    logical rows); an intermediate as large as the whole arena means
    someone materialized every page for every row."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import dispatch
    B, H, K, hd, P, mb, n_pages = 4, 4, 2, 16, 5, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    ak = jax.random.normal(ks[1], (n_pages + 1, P, K, hd))
    av = jax.random.normal(ks[2], (n_pages + 1, P, K, hd))
    pt = jnp.asarray(np.arange(B * (mb + 1)).reshape(B, mb + 1) % n_pages,
                     jnp.int32)
    pos = jnp.asarray([3, 5, 7, 9], jnp.int32)
    jx = jax.make_jaxpr(
        lambda q_: dispatch.paged_attention(q_, ak, av, pt, pos))(q)
    return count_big_intermediates(jx.jaxpr, (n_pages + 1) * P * K * hd)


HOT_PATHS: List[HotPath] = [
    HotPath("fused_logprob_fwd", 0, _logprob_fwd,
            "float intermediates >= T*V in the streamed logprob forward"),
    HotPath("fused_logprob_grad", 3, _logprob_grad,
            "float intermediates >= T*V in the custom-VJP logprob grad "
            "(zeros-init + scan output + aliased carry write)"),
    HotPath("fused_sample_fwd", 0, _sample_fwd,
            "float intermediates >= T*V in the streamed sampler"),
    HotPath("trainer_loss_grad", 3, _trainer_loss_grad,
            "float intermediates >= B*T*V in grad(aipo_loss)"),
    HotPath("attention_chunked", 0, _attention_chunked,
            "float intermediates >= B*H*S*S (full score matrix) in "
            "chunked attention"),
    HotPath("rollout_chunk_retrace", 0, _rollout_retrace,
            "extra rollout_chunk jit entries beyond one per ragged "
            "generate signature"),
    HotPath("engine_admit_retrace", 0, _engine_admit_retrace,
            "extra admit_row jit entries across admissions into "
            "different slots (slot must stay traced data)"),
    HotPath("engine_admit_vocab", 2, _engine_admit_vocab,
            "float intermediates >= R*V in the admission graft beyond "
            "the stitched last_logits write (1 dynamic_update_slice + "
            "its pjit-boundary alias)"),
    HotPath("engine_rows_retrace", 0, _engine_rows_retrace,
            "extra rollout_rows_chunk jit entries across decode rounds "
            "with changed slot occupancy"),
    HotPath("paged_admit_retrace", 0, _paged_admit_retrace,
            "extra admit_row_paged jit entries across admissions into "
            "different slots with different page tables (both must stay "
            "traced data)"),
    HotPath("paged_rows_retrace", 0, _paged_rows_retrace,
            "extra rollout_rows_chunk jit entries across paged decode "
            "rounds with changed occupancy and page-table contents"),
    HotPath("paged_attn_gather", 0, _paged_attn_gather,
            "float intermediates >= the full KV arena in paged "
            "attention (per-row page gathers must stay [B, mb*P]-sized, "
            "never arena-sized)"),
]


def run_hot_paths(names: Optional[List[str]] = None) -> List[Finding]:
    os.environ.setdefault("REPRO_KERNEL_MODE", "ref")
    findings = []
    for hp in HOT_PATHS:
        if names and hp.name not in names:
            continue
        try:
            observed = hp.check()
        except Exception as e:          # tracing itself broke: that gates too
            findings.append(Finding(
                "jaxpr", "hot-path", hp.name, "trace-error",
                type(e).__name__, f"tracing failed: {e!r}"))
            continue
        if observed > hp.budget:
            findings.append(Finding(
                "jaxpr", "hot-path", hp.name, "budget",
                f"over:{hp.budget}",
                f"{observed} > budget {hp.budget}: {hp.what}"))
    return findings


# ------------------------------------------------------- dispatch bypass --

_BYPASS_FNS = {"softmax", "log_softmax"}


def lint_sources(root: Optional[str] = None) -> List[Finding]:
    """Direct jax.nn.softmax/log_softmax outside kernels/ -- candidates
    for full-vocab math bypassing the dispatch layer."""
    findings = []
    for path in iter_source_files(root) if root else iter_source_files():
        rel = relpath(path)
        if f"kernels{os.sep}" in rel:
            continue
        with open(path) as f:
            try:
                tree = ast.parse(f.read(), filename=path)
            except SyntaxError:
                continue
        counts: dict = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _BYPASS_FNS \
                    and isinstance(node.func.value, ast.Attribute) \
                    and node.func.value.attr == "nn":
                fn = node.func.attr
                i = counts.get(fn, 0)
                counts[fn] = i + 1
                findings.append(Finding(
                    "hotpath", rel, "module", "dispatch-bypass",
                    f"{fn}#{i}",
                    f"direct jax.nn.{fn} (line {node.lineno}) "
                    "-- hot paths must route via kernels/dispatch.py",
                    node.lineno))
    return findings


# -------------------------------------------------------- trace staging --

#: modules whose code is (at least partly) staged under jit -- tracing
#: calls there would be dead under trace-time execution or force retraces
_JIT_STAGED = ("kernels" + os.sep, "models" + os.sep,
               os.path.join("rl", "rollout.py"),
               os.path.join("core", "aipo.py"))


def _imports_obs(tree: ast.AST):
    """Yield (lineno, what) for every ``repro.obs`` import in ``tree``."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "repro.obs" or \
                        alias.name.startswith("repro.obs."):
                    yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "repro.obs" or \
                    node.module.startswith("repro.obs."):
                yield node.lineno, node.module
            elif node.module == "repro":
                for alias in node.names:
                    if alias.name == "obs":
                        yield node.lineno, "repro.obs"


def lint_trace_staging(root: Optional[str] = None) -> List[Finding]:
    """No ``repro.obs`` reference inside jit-staged modules: tracing is
    host-side only, and nothing may stage a span into a jitted path."""
    findings = []
    for path in iter_source_files(root) if root else iter_source_files():
        rel = relpath(path)
        tail = rel.split(f"repro{os.sep}", 1)[-1]
        if not tail.startswith(_JIT_STAGED) and tail not in _JIT_STAGED:
            continue
        with open(path) as f:
            try:
                tree = ast.parse(f.read(), filename=path)
            except SyntaxError:
                continue
        for lineno, what in _imports_obs(tree):
            findings.append(Finding(
                "hotpath", rel, "module", "trace-in-jit", what,
                f"imports {what} (line {lineno}) -- repro.obs is "
                "host-side only and must not reach jit-staged code",
                lineno))
    return findings
