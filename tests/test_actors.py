"""Actor/transport contract: wire round-trips, handle endpoint semantics
over both transports, remote-exception re-raise, killed-child fail-fast,
and the acceptance check that a pool-of-1 fixed-staleness controller over
``ProcTransport`` is bit-for-bit the sequential reference."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.llama_paper import smoke
from repro.core import (ActorDied, ActorHandle, CommType,
                        CommunicationChannel, Executor, ExecutorController,
                        GeneratorExecutor, RemoteActorError, RewardExecutor,
                        TrainerExecutor, WeightsCommunicationChannel,
                        as_handle, spawn_actor)
from repro.core import wire
from repro.rl.data import ArithmeticTasks
from repro.rl.rollout import RolloutState, start_rollout

METRIC_KEYS = ("loss", "grad_norm", "mean_ratio", "mean_reward")


def micro_cfg():
    return smoke().replace(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                           head_dim=16, d_ff=64, vocab=64)


def assert_tree_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        if isinstance(x, (jax.Array, np.ndarray)):
            assert isinstance(y, (jax.Array, np.ndarray))
            assert isinstance(x, jax.Array) == isinstance(y, jax.Array), \
                "jax-vs-numpy leaf kind must survive the round-trip"
            xa, ya = np.asarray(x), np.asarray(y)
            assert xa.dtype == ya.dtype and xa.shape == ya.shape
            assert xa.tobytes() == ya.tobytes()      # exact bits
        else:
            assert x == y


# ------------------------------------------------------- wire round-trips --

def test_wire_roundtrip_mixed_pytree_exact_bits():
    key = jax.random.PRNGKey(0)
    tree = {
        "bf16": jax.random.normal(key, (5, 7)).astype(jnp.bfloat16),
        "int8": jnp.arange(-128, 127, dtype=jnp.int8).reshape(5, 51),
        "f32": jax.random.normal(key, (3, 2)) * 1e30,   # extreme values
        "bool": jnp.asarray([True, False, True]),
        "np": np.arange(6, dtype=np.int64).reshape(2, 3),
        "scalar": jnp.float32(3.5),
        "nested": (1, [2.5, "answers"], {"none": None}),
    }
    assert_tree_equal(wire.deserialize(wire.serialize(tree)), tree)


def test_wire_roundtrip_empty_batch():
    """Zero-row batches (an empty emit) keep dtype/shape through the
    dtype/shape header even with no payload bytes."""
    batch = {"tokens": jnp.zeros((0, 12), jnp.int32),
             "behavior_logp": jnp.zeros((0, 12), jnp.float32),
             "mask": np.zeros((0, 12), np.float32),
             "answers": [],
             "prompt_len": 8}
    out = wire.deserialize(wire.serialize(batch))
    assert_tree_equal(out, batch)
    assert out["tokens"].shape == (0, 12)
    assert out["tokens"].dtype == jnp.int32


def test_wire_roundtrip_rollout_state_keeps_static_aux():
    """``RolloutState.prompt_len`` is registered as static pytree aux (a
    Python int through jit); it must come back as exactly that, not as a
    traced/array leaf, or resumed chunks would retrace."""
    from repro.models import init_params
    cfg = micro_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    prompts = jnp.ones((2, 4), jnp.int32)
    state = start_rollout(params, cfg, prompts, 8)
    out = wire.deserialize(wire.serialize(state))
    assert isinstance(out, RolloutState)
    assert type(out.prompt_len) is int and out.prompt_len == 4
    assert_tree_equal(out, state)


def test_wire_non_contiguous_and_transposed_arrays():
    arr = np.arange(24, dtype=np.float32).reshape(4, 6).T   # F-contiguous
    out = wire.deserialize(wire.serialize({"t": arr}))
    np.testing.assert_array_equal(out["t"], arr)


def test_wire_endianness_and_string_dtypes():
    """The dtype token must carry byte order ('>i4' would silently
    byte-swap under a name-based token) and reconstruct unicode/bytes
    dtypes ('str96' is not a constructible dtype name)."""
    tree = {"be": np.arange(4, dtype=">i4"),
            "le": np.arange(4, dtype="<i4"),
            "u": np.array(["12", "345"]),
            "s": np.array([b"ab", b"cdef"])}
    out = wire.deserialize(wire.serialize(tree))
    for k, v in tree.items():
        assert out[k].dtype == v.dtype, k
        np.testing.assert_array_equal(out[k], v)


# --------------------------------------------------------- inproc handles --

def test_as_handle_is_canonical_per_executor():
    ex = Executor("porty")
    h1, h2 = as_handle(ex), as_handle(ex)
    assert h1 is h2 and as_handle(h1) is h1
    assert h1.name == "porty" and h1.role == "generic"
    # channel + controller wiring share the same handle identity
    ch = CommunicationChannel("c", ex, Executor("other"),
                              CommType.BROADCAST)
    assert ch.outbound is h1


def test_inproc_call_resolves_methods_and_attributes():
    ex = Executor("e")
    h = as_handle(ex)
    h.call("put_input", "x", 41)
    assert h.call("get_input", "x") == 41
    assert h.call("curr_step") == 0          # plain attribute read
    assert h.call("ping") == "e"
    assert h.healthy()
    with pytest.raises(AssertionError, match="attribute"):
        h.call("curr_step", 1)               # args to an attribute


# ---------------------------------------------------------- proc executors --

class EchoExecutor(Executor):
    """Importable RPC target for the proc contract tests."""

    role = "echo"

    def pid(self):
        return os.getpid()

    def echo(self, x):
        return x

    def device_world(self):
        """What this actor's own XLA client sees (DeviceSpec tests)."""
        import jax as _jax
        mesh = self.mesh
        return {"n_devices": len(_jax.devices()),
                "mesh_shape": None if mesh is None else
                [int(mesh.shape[a]) for a in mesh.axis_names],
                "mesh_axes": None if mesh is None else
                list(mesh.axis_names)}

    def boom(self):
        raise ValueError("kaboom")

    def sleep(self, t):
        time.sleep(t)
        return "slept"

    def unpicklable_boom(self):
        e = ValueError("gnarly")
        e.payload = lambda: None             # defeats exception pickling
        raise e


def test_proc_actor_runs_in_its_own_process_and_roundtrips():
    h = spawn_actor(EchoExecutor, "remote-echo", transport="proc")
    try:
        assert h.name == "remote-echo" and h.role == "echo"
        assert h.call("pid") != os.getpid()
        payload = {"w": jnp.arange(6, dtype=jnp.bfloat16),
                   "meta": ["a", 3]}
        assert_tree_equal(h.call("echo", payload), payload)
        # cast-then-call is FIFO: the call observes the cast's effect
        h.cast("put_input", "k", 7)
        assert h.call("get_input", "k") == 7
        assert h.call("curr_step") == 0      # attribute read over RPC
        assert h.healthy()
    finally:
        h.close()
    assert not h.healthy()
    with pytest.raises(ActorDied):
        h.call("ping")


def test_proc_remote_exception_reraises_original_type():
    h = spawn_actor(EchoExecutor, "boomer", transport="proc")
    with pytest.raises(ValueError, match="kaboom") as ei:
        h.call("boom")
    assert isinstance(ei.value.__cause__, RemoteActorError)
    assert "boom" in str(ei.value.__cause__)     # remote traceback travels
    # the actor survives its own exception: next call still works
    assert h.call("ping") == "boomer"
    # unpicklable exceptions degrade to RemoteActorError, never a hang
    with pytest.raises(RemoteActorError, match="gnarly"):
        h.call("unpicklable_boom")
    # cast errors surface on the next call through the handle...
    h.cast("boom")
    with pytest.raises(ValueError, match="kaboom"):
        h.call("ping")
    # ...and that call consumed its own reply too: the pipe is not
    # desynced, later calls get *their* results, not their predecessor's
    assert h.call("echo", "after-cast-error") == "after-cast-error"
    assert h.call("pid") != os.getpid()


def test_call_timeout_does_not_poison_the_handle():
    """A per-call timeout abandons that call's reply: when the slow child
    eventually answers, the late reply is discarded instead of being
    delivered to the next caller (which would desync every call after)."""
    h = spawn_actor(EchoExecutor, "slowpoke", transport="proc")
    with pytest.raises(TimeoutError, match="sleep"):
        h.call("sleep", 2.0, timeout=0.3)
    assert h.call("echo", 42) == 42          # not 'slept', not an assert
    assert h.call("ping") == "slowpoke"
    assert h.healthy()


def test_spawn_failure_in_child_constructor_propagates():
    with pytest.raises(ValueError, match="n_per_prompt"):
        spawn_actor(RewardExecutor, n_per_prompt=0, transport="proc")


@pytest.mark.parametrize("transport", ["proc", "shm", "socket"])
def test_local_child_refused_when_parent_holds_tpu(transport, monkeypatch):
    """A chip belongs to one process: once this process has a TPU
    backend, a local child actor fails at once instead of waiting out
    the spawn handshake."""
    from jax._src import xla_bridge
    jax.devices()                                # backend initialized
    assert xla_bridge.backends_are_initialized()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="already holds the TPU"):
        spawn_actor(EchoExecutor, "child", transport=transport)
    assert time.monotonic() - t0 < 5.0
    h = spawn_actor(EchoExecutor, "here", transport="inproc")
    assert h.call("ping") == "here"


def test_killed_child_raises_actor_died_not_hang():
    h = spawn_actor(EchoExecutor, "victim", transport="proc")
    assert h.call("ping") == "victim"
    h.transport._proc.kill()
    t0 = time.monotonic()
    with pytest.raises(ActorDied, match="exited"):
        h.call("ping", timeout=30.0)
    assert time.monotonic() - t0 < 10.0      # liveness poll, not deadline
    assert not h.healthy()


# ------------------------------------------------ shm / device-spec extras --

def test_shm_ring_reuse_and_growth_exact_bytes():
    """Payloads over the threshold ride ring slots; repeated echoes
    recycle slots and a payload larger than any existing slot grows one
    -- every byte exact throughout."""
    h = spawn_actor(EchoExecutor, "shm-echo", transport="shm")
    try:
        rng = np.random.default_rng(7)
        mid = {"w": rng.standard_normal((256, 300)).astype(np.float32),
               "q": jnp.arange(123, dtype=jnp.bfloat16), "meta": ["x", 1]}
        for _ in range(5):                   # slot recycling
            assert_tree_equal(h.call("echo", mid), mid)
        big = {"w": rng.standard_normal(3_000_000).astype(np.float32)}
        assert_tree_equal(h.call("echo", big), big)   # forces ring growth
        assert_tree_equal(h.call("echo", mid), mid)   # small again after
        # casts and calls stay FIFO through the shm plane
        h.cast("put_input", "k", 11)
        assert h.call("get_input", "k") == 11
    finally:
        h.close()


@pytest.mark.parametrize("transport", ["proc", "socket"])
def test_device_spec_pins_child_device_world(transport):
    """A spawned child owns its own XLA client: the spec's emulated
    device count and mesh shape must show up in the *child*, while this
    process keeps its single CPU device."""
    from repro.core import DeviceSpec
    h = spawn_actor(EchoExecutor, "dev-probe", transport=transport,
                    device_spec=DeviceSpec(device_count=2,
                                           mesh_shape=(1, 2)))
    try:
        world = h.call("device_world")
        assert world["n_devices"] == 2
        assert world["mesh_shape"] == [1, 2]
        assert world["mesh_axes"] == ["data", "model"]
        assert len(jax.devices()) == 1       # parent untouched
        assert h.mesh is None                # the mesh lives with the child
    finally:
        h.close()


# ------------------------------------------- controller over ProcTransport --

def build_controller(seed, staleness, max_steps, transport, chunk=0,
                     gen_holder=None):
    cfg = micro_cfg()
    tasks = ArithmeticTasks(prompt_len=8, max_operand=4, ops="+", seed=seed)
    gen = spawn_actor(GeneratorExecutor, cfg, tasks, n_prompts=4,
                      n_per_prompt=2, max_new=4, temperature=1.0,
                      seed=seed, chunk=chunk, transport=transport)
    if gen_holder is not None:
        gen_holder.append(gen)
    rew = RewardExecutor(n_per_prompt=2)
    trn = TrainerExecutor(cfg, lr=5e-2, seed=seed)
    return ExecutorController(
        [gen, rew, trn],
        [WeightsCommunicationChannel("policy_model", trn, gen),
         CommunicationChannel("completions", gen, rew, CommType.GATHER),
         CommunicationChannel("completions_with_reward", rew, trn,
                              CommType.SCATTER)],
        max_steps=max_steps, mode="async", staleness=staleness,
        timeout=300.0)


@pytest.mark.parametrize("chunk", [0, 2])
def test_proc_pool_of_one_matches_run_sequential_bit_for_bit(chunk):
    """The tentpole acceptance check: the generator living in a spawned
    subprocess with its own XLA client -- payloads serialized over the
    pipe, weights cast version by version -- trains bit-for-bit the run
    the all-inproc sequential reference trains.  ``chunk=2`` routes the
    partial-rollout scheduler's job/state round-trips over the RPC
    boundary too."""
    threaded = build_controller(seed=11, staleness=1, max_steps=3,
                                transport="proc", chunk=chunk)
    sequential = build_controller(seed=11, staleness=1, max_steps=3,
                                  transport="inproc", chunk=chunk)
    ht = threaded.run()
    hs = sequential.run_sequential()
    assert [[h[k] for k in METRIC_KEYS] for h in ht] == \
        [[h[k] for k in METRIC_KEYS] for h in hs]
    assert [h["weight_version"] for h in ht] == \
        [h["weight_version"] for h in hs] == [0, 0, 1]


def test_controller_reraises_when_child_killed_mid_run():
    """A generator child dying mid-run must unwind the controller with
    ``ActorDied`` -- closed queues wake every blocked thread -- instead
    of wedging the worker on a pipe nobody will write."""
    holder = []

    class KillerTrainer(TrainerExecutor):
        def step(self):
            if self.curr_step >= 1:
                holder[0].transport._proc.kill()
            return super().step()

    cfg = micro_cfg()
    tasks = ArithmeticTasks(prompt_len=8, max_operand=4, ops="+", seed=3)
    gen = spawn_actor(GeneratorExecutor, cfg, tasks, n_prompts=4,
                      n_per_prompt=2, max_new=4, temperature=1.0, seed=3,
                      transport="proc")
    holder.append(gen)
    rew = RewardExecutor(n_per_prompt=2)
    trn = KillerTrainer(cfg, lr=5e-2, seed=3)
    ctl = ExecutorController(
        [gen, rew, trn],
        [WeightsCommunicationChannel("policy_model", trn, gen),
         CommunicationChannel("completions", gen, rew, CommType.GATHER),
         CommunicationChannel("completions_with_reward", rew, trn,
                              CommType.SCATTER)],
        max_steps=6, mode="async", staleness=1, timeout=120.0)
    t0 = time.monotonic()
    with pytest.raises(ActorDied):
        ctl.run()
    assert time.monotonic() - t0 < 60.0
    assert ctl._sample_queue.closed          # shutdown() ran
