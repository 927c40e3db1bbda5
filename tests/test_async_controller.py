"""Threaded async controller: bit-for-bit equivalence with the sequential
reference, the bounded-staleness weight schedule, metrics recording,
continuation across run() calls, and failure propagation.

Generators are constructed through ``spawn_actor``, so running this suite
with ``REPRO_TRANSPORT=proc`` hosts every generator in its own spawned
process (CI's multi-device job does exactly that) -- same assertions,
different placement."""
import threading
import time

import numpy as np
import pytest

from repro.configs.llama_paper import smoke
from repro.core import (AsyncExecutorController, CommType,
                        CommunicationChannel, ExecutorController,
                        GeneratorExecutor, RewardExecutor, StalenessBuffer,
                        TrainerExecutor, WeightsCommunicationChannel,
                        spawn_actor)
from repro.rl.data import ArithmeticTasks

# training metrics that must agree exactly between threaded and sequential
METRIC_KEYS = ("loss", "grad_norm", "mean_ratio", "mean_reward")


def micro_cfg():
    return smoke().replace(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                           head_dim=16, d_ff=64, vocab=64)


def build(seed=0, staleness=1, max_steps=4, mode="async", gen_cls=None,
          timeout=120.0, chunk=0, pool=None):
    cfg = micro_cfg()
    tasks = ArithmeticTasks(prompt_len=8, max_operand=4, ops="+", seed=seed)
    gen_cls = gen_cls or GeneratorExecutor
    gen = spawn_actor(gen_cls, cfg, tasks, n_prompts=4, n_per_prompt=2,
                      max_new=4, temperature=1.0, seed=seed, chunk=chunk)
    rew = RewardExecutor(n_per_prompt=2)
    trn = TrainerExecutor(cfg, lr=5e-2, seed=seed)
    return ExecutorController(
        [gen, rew, trn],
        [WeightsCommunicationChannel("policy_model", trn, gen),
         CommunicationChannel("completions", gen, rew, CommType.GATHER),
         CommunicationChannel("completions_with_reward", rew, trn,
                              CommType.SCATTER)],
        max_steps=max_steps, mode=mode, staleness=staleness, timeout=timeout,
        pool=pool)


def metrics(history):
    return [[h[k] for k in METRIC_KEYS] for h in history]


# ------------------------------------------------- threaded == sequential --

@pytest.mark.parametrize("staleness", [1, 2, 3])
@pytest.mark.parametrize("chunk", [0, 2])
def test_threaded_matches_sequential_bit_for_bit(staleness, chunk):
    """The tentpole acceptance check: real threads change wall-clock
    overlap, never numerics -- weight versions are pinned by count.
    ``chunk=2`` exercises the pool's chunk-scheduled partial-rollout path
    (``max_new=4`` -> two resumable chunks per batch) against the
    monolithic sequential reference."""
    threaded = build(seed=11, staleness=staleness, max_steps=4, chunk=chunk)
    assert isinstance(threaded, AsyncExecutorController)
    sequential = build(seed=11, staleness=staleness, max_steps=4,
                       chunk=chunk)
    ht = threaded.run()
    hs = sequential.run_sequential()
    assert metrics(ht) == metrics(hs)        # exact float equality
    assert [h["weight_version"] for h in ht] == \
        [h["weight_version"] for h in hs]
    assert [h["weight_version"] for h in ht] == \
        [max(0, n - staleness) for n in range(4)]


def test_mixing_threaded_and_sequential_runs_raises():
    """One controller, one entry point: threaded and sequential runs keep
    weight state in different places, so continuing across modes would
    deliver retired weight versions (or deadlock)."""
    ctl = build(seed=2, staleness=1, max_steps=2)
    ctl.run()
    with pytest.raises(RuntimeError, match="fresh controller"):
        ctl.run_sequential()
    ctl2 = build(seed=2, staleness=1, max_steps=2)
    ctl2.run_sequential()
    with pytest.raises(RuntimeError, match="fresh controller"):
        ctl2.run()


def test_continuation_matches_single_run():
    """run() called twice continues the schedule exactly where it left
    off: counters, channel queues and RNG state all persist."""
    split = build(seed=5, staleness=1, max_steps=2)
    split.run()
    split.run()
    whole = build(seed=5, staleness=1, max_steps=4)
    whole.run()
    assert metrics(split.history) == metrics(whole.history)


# -------------------------------------------- bounded-staleness schedule --

def test_weight_version_schedule_and_bound():
    s = 2
    ctl = build(seed=3, staleness=s, max_steps=5)
    hist = ctl.run()
    for n, h in enumerate(hist):
        assert h["weight_version"] == max(0, n - s)
        assert h["trainer_version"] == n + 1
        assert h["sample_staleness"] == min(n, s) <= s
    assert max(ctl.staleness_hist) <= s
    assert sum(ctl.staleness_hist.values()) == len(hist)


def test_staleness_buffer_delivers_tick_minus_staleness():
    """Regression for the seed's _sync_weights off-by-one: at staleness=1
    the ad-hoc deque delivered the weights pushed the *same* tick (zero-
    step lag).  The unified StalenessBuffer schedule delivers exactly
    version ``tick - staleness``."""
    for s in (1, 2, 3):
        buf = StalenessBuffer(delay=s)
        buf.push(0, "w0")                    # init publish (version 0)
        assert buf.pop() is None             # not released while fresh
        for tick in range(1, 8):
            buf.push(tick, f"w{tick}")
            released = buf.pop()
            if tick < s:
                assert released is None      # still on the init weights
            else:
                version, payload = released
                assert version == tick - s   # NOT the same-tick push
                assert payload == f"w{tick - s}"


def test_controller_history_records_async_metrics():
    ctl = build(seed=1, staleness=1, max_steps=3)
    hist = ctl.run()
    for h in hist:
        for key in ("weight_version", "trainer_version", "sample_staleness",
                    "queue_depth", "gen_idle_s", "train_idle_s"):
            assert key in h
        assert h["queue_depth"] >= 0
        assert h["gen_idle_s"] >= 0 and h["train_idle_s"] >= 0
    for key in ("wall_s", "gen_busy_s", "train_busy_s", "overlap_s",
                "gen_idle_s", "train_idle_s"):
        assert key in ctl.stats
    assert ctl.stats["gen_busy_s"] > 0 and ctl.stats["train_busy_s"] > 0


def test_two_live_weight_channels_both_drained():
    """Every weight channel into the generator must be drained each
    version, or its bounded queue wedges the consumer's send."""
    cfg = micro_cfg()
    tasks = ArithmeticTasks(prompt_len=8, max_operand=4, ops="+", seed=2)
    gen = spawn_actor(GeneratorExecutor, cfg, tasks, n_prompts=4,
                      n_per_prompt=2, max_new=4, seed=2)
    rew = RewardExecutor(n_per_prompt=2)
    trn = TrainerExecutor(cfg, lr=5e-2, seed=2)
    ctl = ExecutorController(
        [gen, rew, trn],
        [WeightsCommunicationChannel("policy_model", trn, gen),
         WeightsCommunicationChannel("policy_model", trn, gen),
         CommunicationChannel("completions", gen, rew, CommType.GATHER),
         CommunicationChannel("completions_with_reward", rew, trn,
                              CommType.SCATTER)],
        max_steps=8, mode="async", staleness=1, timeout=60.0)
    hist = ctl.run()                         # would deadlock pre-fix
    assert len(hist) == 8
    for ch in ctl._live_weight_channels:
        assert ch.pending() <= ctl.staleness + 1


@pytest.mark.parametrize("staleness", [1, 3])
def test_kl_reference_pipeline_threaded_matches_sequential(staleness):
    """Weight channels that feed non-generator executors (the frozen KL
    reference) are serviced on the consumer thread with the same delayed
    schedule as the sequential path -- including through the pool's
    chunk-scheduled partial-rollout path (``chunk=2``)."""
    from repro.core import RefPolicyExecutor

    def build_kl(seed):
        cfg = micro_cfg()
        tasks = ArithmeticTasks(prompt_len=8, max_operand=4, ops="+",
                                seed=seed)
        gen = spawn_actor(GeneratorExecutor, cfg, tasks, n_prompts=4,
                          n_per_prompt=2, max_new=4, seed=seed, chunk=2)
        ref = RefPolicyExecutor(cfg)
        rew = RewardExecutor(n_per_prompt=2)
        trn = TrainerExecutor(cfg, lr=5e-2, kl_coef=0.1, seed=seed)
        return ExecutorController(
            [gen, ref, rew, trn],
            [WeightsCommunicationChannel("policy_model", trn, gen),
             WeightsCommunicationChannel("policy_model", trn, ref),
             CommunicationChannel("completions", gen, ref,
                                  CommType.BROADCAST),
             CommunicationChannel("completions_with_ref", ref, rew,
                                  CommType.GATHER),
             CommunicationChannel("completions_with_reward", rew, trn,
                                  CommType.SCATTER)],
            max_steps=4, mode="async", staleness=staleness, timeout=120.0)

    threaded, sequential = build_kl(9), build_kl(9)
    ht = threaded.run()
    hs = sequential.run_sequential()
    assert metrics(ht) == metrics(hs)


# -------------------------------------------------- failure propagation --

class _ExplodingGenerator(GeneratorExecutor):
    """Raises from both the chunk-stepping admission hook (pool path) and
    the monolithic ``step()`` (sequential / complete-batch path)."""

    def begin_batch(self, batch_index=None):
        if self.curr_step >= 1:
            raise RuntimeError("generator exploded")
        return super().begin_batch(batch_index)


def test_generator_exception_propagates_and_joins():
    ctl = build(seed=0, staleness=1, max_steps=6,
                gen_cls=_ExplodingGenerator, timeout=60.0)
    before = threading.active_count()
    with pytest.raises(RuntimeError, match="generator exploded"):
        ctl.run()
    deadline = time.monotonic() + 10
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before   # no leaked threads


class _SlowGenerator(GeneratorExecutor):
    def begin_batch(self, batch_index=None):
        time.sleep(0.4)
        return super().begin_batch(batch_index)


def test_run_longer_than_timeout_keeps_training():
    """``timeout`` bounds a stall, not the whole run: a run that trains a
    step every half second goes on past its ``timeout``."""
    ctl = build(seed=0, staleness=1, max_steps=2, gen_cls=_SlowGenerator)
    ctl.run()                                   # compiles every program
    ctl.timeout, ctl.max_steps = 2.0, 8
    t0 = time.monotonic()
    history = ctl.run()
    assert time.monotonic() - t0 > ctl.timeout
    assert len(history) == 10


def test_consumer_exception_unblocks_pool_and_joins():
    """A trainer-side failure must close the comms so workers blocked in
    channel recv / queue push unwind with ``Closed`` and join -- the
    deterministic shutdown path (no daemon-thread leaks)."""

    class _ExplodingTrainer(TrainerExecutor):
        def step(self):
            if self.curr_step >= 2:
                raise RuntimeError("trainer exploded")
            return super().step()

    cfg = micro_cfg()
    tasks = ArithmeticTasks(prompt_len=8, max_operand=4, ops="+", seed=4)
    gen = spawn_actor(GeneratorExecutor, cfg, tasks, n_prompts=4,
                      n_per_prompt=2, max_new=4, seed=4)
    rew = RewardExecutor(n_per_prompt=2)
    trn = _ExplodingTrainer(cfg, lr=5e-2, seed=4)
    ctl = ExecutorController(
        [gen, rew, trn],
        [WeightsCommunicationChannel("policy_model", trn, gen),
         CommunicationChannel("completions", gen, rew, CommType.GATHER),
         CommunicationChannel("completions_with_reward", rew, trn,
                              CommType.SCATTER)],
        max_steps=8, mode="async", staleness=1, timeout=60.0)
    before = threading.active_count()
    with pytest.raises(RuntimeError, match="trainer exploded"):
        ctl.run()
    deadline = time.monotonic() + 10
    while threading.active_count() > before and time.monotonic() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before   # no leaked threads
    assert ctl._sample_queue.closed             # shutdown() ran


# -------------------------------------------------- StalenessBuffer core --

def test_staleness_buffer_fifo_mode_is_threaded_queue():
    """delay=0, bounded: a producer/consumer queue with backpressure."""
    buf = StalenessBuffer(delay=0, max_size=2)
    got = []

    def consumer():
        for _ in range(5):
            got.append(buf.pop_wait(timeout=5.0))

    t = threading.Thread(target=consumer)
    t.start()
    for i in range(5):
        buf.push(i, f"b{i}", timeout=5.0)
    t.join(timeout=5.0)
    assert not t.is_alive()
    assert got == [(i, f"b{i}") for i in range(5)]
    assert len(buf) == 0


def test_staleness_buffer_push_timeout_when_full():
    buf = StalenessBuffer(delay=0, max_size=1)
    buf.push(0, "b0")
    with pytest.raises(TimeoutError):
        buf.push(1, "b1", timeout=0.05)
