"""The main-path Pallas kernels compile for a TPU v5e at StarCoder2-3B
widths (d_model 3072, 24 query / 2 KV heads, head_dim 128, d_ff 12288,
vocab 49152).

Nothing runs: each kernel is lowered with ``interpret=False`` against a
*described* v5e topology and handed to the TPU compiler, which refuses
what Mosaic cannot tile (misaligned blocks, unsupported casts, ops with
no lowering) exactly as it would on the chip.  The topology is described
inside a module fixture -- never at import -- because only one process
at a time may load the TPU library.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_logprob import fused_logprob, fused_logprob_bwd
from repro.kernels.fused_sample import fused_sample
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.paged_attention import paged_attention_kernel

D_MODEL, D_FF, VOCAB = 3072, 12288, 49152
N_HEADS, N_KV, HEAD_DIM = 24, 2, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a described-device compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# 608 = 32 rows x 19 action positions, the launcher's default train batch:
# the row blocks then tile a longer (padded) array
@pytest.mark.parametrize("T", [256, 608])
def test_fused_logprob_fwd_bwd_compile(spec, T):
    logits = spec((T, VOCAB), jnp.float32)
    vec = spec((T,), jnp.float32)
    _compile(functools.partial(fused_logprob, return_stats=True),
             logits, spec((T,), jnp.int32))
    _compile(fused_logprob_bwd, logits, spec((T,), jnp.int32), vec, vec, vec)


@pytest.mark.parametrize("B,temperature", [(32, 1.0), (32, 0.0),
                                           (512, 1.0)])
def test_fused_sample_compiles(spec, B, temperature):
    _compile(functools.partial(fused_sample, temperature=temperature),
             spec((B, VOCAB), jnp.float32), spec((2,), jnp.uint32))


def _custom_call_signatures(text):
    """((dtype, rank) of the result, of each operand) of each
    ``tpu_custom_call`` in a compiled module, in the form of
    ``bench.kernels.SIGNATURES``."""
    from bench import kernels
    arrays = lambda s: tuple((t, len(d.split(","))) for t, d in
                             kernels._ARRAY.findall(s))
    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        result = line.split("=", 1)[1].split("custom-call(")[0]
        operands = re.search(r"operand_layout_constraints=\{(.*?\})\}",
                             line).group(1)
        out.append((arrays(result), arrays(operands)))
    return out


def _custom_call_signature(text):
    """The signature of the one ``tpu_custom_call`` in a compiled
    module."""
    (sig,) = _custom_call_signatures(text)
    return sig


def _computations(text):
    """({name: instruction lines}, the entry's name) of a compiled
    module."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                entry = name
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line.strip())
    return comps, entry


def _copied_shapes(comps, name):
    """Result dims of each slice, dynamic slice and copy in computation
    ``name``, and of each fusion it calls that holds one (with theirs);
    not of the loops it runs."""
    out = []
    for line in comps[name]:
        result = re.search(r" = \w+\[([\d,]*)\]", line)
        dims = result and tuple(int(d) for d in result.group(1).split(",")
                                if d)
        fused = re.search(r"\sfusion\(.*calls=%([\w.\-]+)", line)
        inner = fused and _copied_shapes(comps, fused.group(1))
        if inner:
            out += inner
        if result and (inner or re.search(r"\s(slice|dynamic-slice|copy)\(",
                                          line)):
            out.append(dims)
    return out


def _arena_relayouts(text, n_pages):
    """Reshapes and copies of an arena-sized array in a compiled module."""
    return [ln.strip() for ln in text.splitlines()
            if re.search(rf"\[{n_pages + 1},\d", ln)
            and re.search(r"\s(reshape|copy|transpose)\(", ln)]


# StarCoder2-3B's decode cell (24 / 2 heads, windowed), and 8 kv heads
@pytest.mark.parametrize("n_heads,n_kv,window", [(N_HEADS, N_KV, 4096),
                                                 (64, 8, 0)])
def test_paged_attention_compiles(spec, n_heads, n_kv, window):
    """The kernel compiles at the cell's shapes, its custom call keeps the
    signature the benchmark tells it by, and the arena reaches it in
    the layout it is stored in: no relayout of the arena around it."""
    from bench import kernels
    B, P, mb = 16, 16, 33
    n_pages = B * mb
    arena = spec((n_pages + 1, P, n_kv * HEAD_DIM), jnp.float32)
    text = _compile(functools.partial(paged_attention_kernel, window=window),
                    spec((B, n_heads, HEAD_DIM), jnp.float32), arena, arena,
                    spec((B, mb + 1), jnp.int32),
                    spec((B,), jnp.int32)).as_text()
    assert _custom_call_signature(text) in \
        kernels.SIGNATURES["paged_attention"]
    assert _arena_relayouts(text, n_pages) == []


def test_paged_decode_chunk_compiles(spec, monkeypatch):
    """The engine's decode chunk at the cell's shapes (16 slots of 12 +
    512 positions, pages of 16, one layer) on the compiled kernel route:
    the per-step KV write lands in the arena's stored layout, and no
    arena-sized relayout is left in the program."""
    from repro import configs
    from repro.models import init_params
    from repro.rl.rollout import rollout_rows_chunk, start_row_pool
    monkeypatch.setenv("REPRO_KERNEL_MODE", "compile")
    cfg = configs.get_config("starcoder2-3b").replace(n_layers=1)
    as_spec = lambda tree: jax.tree.map(lambda a: spec(a.shape, a.dtype),
                                        tree)
    params = as_spec(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.float32), jax.random.PRNGKey(0)))
    state = as_spec(jax.eval_shape(lambda: start_row_pool(
        cfg, 16, 12 + 512, 12, kv_layout="paged", kv_page_size=16)))
    n_pages = 16 * 33
    assert state.cache["segments"][0]["k"].shape == \
        (1, n_pages + 1, 16, N_KV * HEAD_DIM)
    compiled = rollout_rows_chunk.lower(params, cfg, state,
                                        spec((2,), jnp.uint32), n_steps=16
                                        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _arena_relayouts(text, n_pages) == []
    # its one attention segment is its whole layer stack: taking the
    # segment once per chunk changes nothing in this program
    assert compiled.memory_analysis().temp_size_in_bytes == 378_713_088


def test_flash_attention_compiles(spec):
    S = 2048
    q = spec((1, S, N_HEADS, HEAD_DIM), jnp.float32)
    kv = spec((1, S, N_KV, HEAD_DIM), jnp.float32)
    _compile(flash_attention, q, kv, kv)


def test_prefill_kv_write_compiles(spec):
    """``start_rollout`` at full width, one layer: the prefill writes its
    K and V into the cache as slice updates, which the TPU compiler takes
    (it aborts the whole process on the scatter pair they replace)."""
    from repro import configs
    from repro.models import init_params
    from repro.rl.rollout import start_rollout
    cfg = configs.get_config("starcoder2-3b").replace(n_layers=1)
    params = jax.tree.map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(lambda k: init_params(cfg, k, jnp.float32),
                       jax.random.PRNGKey(0)))
    compiled = start_rollout.lower(params, cfg, spec((32, 12), jnp.int32),
                                   20).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 4 * 3e8


def test_int8_matmul_compiles(spec):
    _compile(int8_matmul, spec((256, D_MODEL), jnp.float32),
             spec((D_MODEL, D_FF), jnp.int8), spec((D_FF,), jnp.float32))


# Mellum2-12B-A2.5B's expert layer on one chip of 8 (8 experts of
# d 2304 x 896 held): a decode step's 16 rows (row tile 8) and a train
# step's 8 rows of 513 (row tile 256), each product and its gradients
@pytest.mark.parametrize("M,tm", [(184, 8), (35072, 256)])
@pytest.mark.parametrize("K,N", [(2304, 896), (896, 2304)])
def test_grouped_matmul_compiles(spec, M, tm, K, N):
    """The kernels compile at the cell's shapes and keep the signatures
    the benchmark tells them by."""
    from bench import expert_matmul
    from repro.kernels.grouped_matmul import gmm, tgmm
    tg, nu = spec((M // tm,), jnp.int32), spec((1,), jnp.int32)
    w = spec((8, K, N), jnp.float32)
    cases = (
        (functools.partial(gmm, tm=tm), (spec((M, K), jnp.float32), w, tg, nu),
         "gmm"),
        (functools.partial(gmm, tm=tm, transpose_rhs=True),
         (spec((M, N), jnp.float32), w, tg, nu), "gmm"),
        (functools.partial(tgmm, tm=tm),
         (spec((M, K), jnp.float32), spec((M, N), jnp.float32),
          spec((8,), jnp.int32), tg, nu), "tgmm"))
    for fn, args, kind in cases:
        text = _compile(fn, *args).as_text()
        assert _custom_call_signature(text) in \
            expert_matmul.SIGNATURES[kind]


def test_mellum_decode_chunk_compiles(spec, monkeypatch):
    """The engine's decode chunk for the Mellum2 cell (16 slots of 12 +
    512 positions, pages of 16, 4 layers, 8 of 64 experts held) on the
    kernel routes: three grouped products per layer beside the paged
    attention kernel.  The weights of its two attention segments (3
    sliding layers, 1 full) are sliced out of the layer stack once, in
    the entry computation, and not in each step of the chunk's loop."""
    from repro import configs
    from repro.models import init_params
    from repro.rl.rollout import rollout_rows_chunk, start_row_pool
    monkeypatch.setenv("REPRO_KERNEL_MODE", "compile")
    cfg = configs.get_config("mellum2-12b-a2.5b").replace(
        n_layers=4, vocab=12288, experts_held=8)
    as_spec = lambda tree: jax.tree.map(lambda a: spec(a.shape, a.dtype),
                                        tree)
    params = as_spec(jax.eval_shape(
        lambda k: init_params(cfg, k, jnp.float32), jax.random.PRNGKey(0)))
    state = as_spec(jax.eval_shape(lambda: start_row_pool(
        cfg, 16, 12 + 512, 12, kv_layout="paged", kv_page_size=16)))
    text = rollout_rows_chunk.lower(params, cfg, state,
                                    spec((2,), jnp.uint32), n_steps=16
                                    ).compile().as_text()
    from bench import expert_matmul
    gmm = set(expert_matmul.SIGNATURES["gmm"])
    # gate, up and down in the body of each of the two layer segments
    # (3 sliding, 1 full), and no weight gradient
    assert sum(sig in gmm for sig in _custom_call_signatures(text)) == 3 * 2
    comps, entry = _computations(text)
    (step_loop,) = [re.search(r"body=%([\w.\-]+)", line).group(1)
                    for line in comps[entry] if " while(" in line]
    stack = [a.shape for a in jax.tree.leaves(params["moe_layers"])]
    segments = {(n,) + s[1:] for s in stack for n in (3, 1)}
    layer_experts = {s[1:] for s in stack if len(s) == 4}
    in_loop = set(_copied_shapes(comps, step_loop))
    assert not in_loop & segments
    assert not in_loop & layer_experts
    expert_segments = [(n,) + s[1:] for s in stack if len(s) == 4
                       for n in (3, 1)]
    assert sorted(d for d in _copied_shapes(comps, entry)
                  if d in expert_segments) == sorted(expert_segments)
