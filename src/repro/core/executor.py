"""Executors (paper Sec. 5.1.1): self-contained units owning a model, a
device (sub)mesh, and one RL pipeline stage.

Mirrors the paper's base-class contract: init / step / save_checkpoint /
get_output(+get_model).  Each executor jits its computation onto its own
submesh, which is what lets the controller's async dispatch overlap trainer
and generator work on disjoint devices.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ddma
from repro.core.aipo import token_logprobs
from repro.obs import trace as obs_trace
from repro.rl import data as rl_data
from repro.rl import rewards as rl_rewards
from repro.rl.rollout import action_mask, finalize_rollout, rollout_chunk, \
    start_rollout
from repro.rl.scheduler import RolloutJob
from repro.train.trainstep import TrainState, init_train_state, \
    make_train_step


class PinnedParams:
    """Marker standing in for ``RolloutJob.params`` when the admission-
    time weight snapshot is *pinned* inside the generator actor
    (``begin_batch_pinned``): the job round-trips a tiny reference over
    the transport instead of the whole pytree; ``emit_batch`` releases
    the pin."""

    __slots__ = ("key",)

    def __init__(self, key: int):
        self.key = key


class Executor:
    """Base executor (paper Sec. 5.1.1).

    Input/output ports are lock-guarded so channels may hand payloads
    across controller threads; each executor's ``step`` itself is only
    ever driven by the single thread that owns it.
    """

    role = "generic"

    def __init__(self, name: str, mesh=None):
        self.name = name
        self.mesh = mesh
        self.curr_step = 0
        self._port_lock = threading.RLock()
        self._outputs: Dict[str, Any] = {}
        self._inputs: Dict[str, Any] = {}
        self._staged_weights: Dict[int, Any] = {}

    def init(self):
        pass

    def _on_mesh(self):
        """Context for tracing and running this executor's programs: its
        submesh, so eager work lands there and the kernel dispatch runs
        Pallas kernels per device (none without a submesh)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _place(self, tree):
        """Replicate ``tree`` over this executor's submesh (identity
        without one), so its work runs on its own devices."""
        if self.mesh is None:
            return tree
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def set_step(self, i: int):
        self.curr_step = i

    def step(self):
        raise NotImplementedError

    def get_output(self, name: str):
        with self._port_lock:
            return self._outputs[name]

    def set_output(self, name: str, value):
        with self._port_lock:
            self._outputs[name] = value

    def put_input(self, name: str, value):
        with self._port_lock:
            self._inputs[name] = value

    def get_input(self, name: str, default=None):
        with self._port_lock:
            return self._inputs.get(name, default)

    def ping(self) -> str:
        """Health endpoint: a live actor answers with its name."""
        return self.name

    def chaos_hang(self, seconds: float):
        """Fault-injection endpoint (FaultPlan 'hang'): wedge this
        actor's server loop so the caller's ``call_timeout`` fires and
        the supervisor's hang-vs-slow triage can be exercised."""
        time.sleep(float(seconds))

    # ------------------------------------------- weight-fabric slot surface --
    # The weight-sync fabric (repro.core.fabric) separates *publication*
    # from *application*: ``stage_weights`` parks a versioned snapshot in
    # a slot -- for a remote actor this is where the shm/socket transfer
    # lands, overlapped with whatever the actor is computing -- and the
    # tiny ``commit_weights`` cast later flips the executor to that slot
    # at a staleness-legal boundary.  The previous slot's params stay
    # alive (jax arrays are refcounted; in-flight jobs pin their own
    # admission snapshot) until every reader drops them -- the paper's
    # "generation never blocks on weight transfer" property.

    def stage_weights(self, params, version: int):
        """Park a published weight snapshot without applying it.

        Slots are refcounted: several channels publishing the same
        version into one actor stage/commit it once each, exactly like
        the old path delivered (idempotent) ``set_weights`` per
        channel."""
        with self._port_lock:
            cur = self._staged_weights.get(version)
            self._staged_weights[version] = \
                (params, 1 if cur is None else cur[1] + 1)

    def commit_weights(self, version: int):
        """Apply a previously staged snapshot; release its slot once
        every stager's commit arrived."""
        with self._port_lock:
            params, n = self._staged_weights[version]
            if n <= 1:
                self._staged_weights.pop(version)
            else:
                self._staged_weights[version] = (params, n - 1)
        self.set_weights(params, version=version)

    def staged_versions(self):
        """Versions currently staged but not yet committed (tests)."""
        with self._port_lock:
            return sorted(self._staged_weights)

    def configure(self, **attrs):
        """Set existing executor attributes by name -- the handle-API
        replacement for poking attributes on a raw executor (a process-
        backed actor's attributes live in its own process)."""
        for k, v in attrs.items():
            assert hasattr(self, k), \
                f"executor '{self.name}' has no attribute {k!r}"
            setattr(self, k, v)

    def step_snapshot(self, names):
        """``step()`` + output-port snapshot in one endpoint: a remote
        caller pays one round-trip and one payload for a completed batch
        instead of a discarded step() return plus a get_output refetch."""
        self.step()
        return {n: self.get_output(n) for n in names}

    def save_checkpoint(self, path: str, step: int):
        pass


class GeneratorExecutor(Executor):
    """Policy inference: rollouts + behavior logprobs (+ optional int8).

    Chunk-stepping: ``begin_batch`` / ``advance_chunk`` / ``emit_batch``
    are the resumable-rollout hooks the ``RolloutScheduler`` drives (one
    ``rollout_chunk`` per ``advance_chunk``, state parked between calls);
    the monolithic ``step()`` is the same three hooks run back to back, so
    both paths emit bit-for-bit identical batches.
    """

    role = "generator"

    def __init__(self, cfg, tasks: rl_data.ArithmeticTasks, *,
                 n_prompts: int, n_per_prompt: int, max_new: int,
                 temperature: float = 1.0, quantize: bool = False,
                 chunk: int = 0, seed: int = 0, mesh=None,
                 name: str = "generator"):
        super().__init__(name, mesh)
        self.cfg = cfg
        self.tasks = tasks
        self.n_prompts = n_prompts
        self.n_per_prompt = n_per_prompt
        self.max_new = max_new
        self.temperature = temperature
        self.quantize = quantize
        self.chunk = chunk
        self.key = self._place(jax.random.PRNGKey(seed))
        self.params = None
        self.weight_version = -1        # version of self.params (-1 = unset)
        self._pinned: Dict[int, Any] = {}    # admission snapshots by pin key
        self._pin_seq = 0
        self._engine = None             # lazy RolloutEngine (engine mode)

    def set_weights(self, params, version: Optional[int] = None):
        """Receives DDMA'd trainer weights; applies generator quantization.
        ``version`` tags which trainer update produced these weights, so
        every batch this executor emits can be staleness-checked.
        Versions only move forward: a delivery older than the current
        weights (possible when a supervised replay races regular channel
        drains around a respawn) is dropped, never applied."""
        if version is not None and version < self.weight_version:
            return
        self.params = ddma.quantize_dequant(params) if self.quantize \
            else params
        if version is not None:
            self.weight_version = version

    # ------------------------------------------------ chunk-stepping hooks --

    def begin_batch(self, batch_index: Optional[int] = None):
        """Sample a task batch, split its per-batch key and prefill.

        Returns ``(job, state)`` ready for ``advance_chunk``.  Task
        sampling and key splitting happen here, in admission order, so a
        single worker admitting batches in index order consumes exactly
        the RNG stream the monolithic ``step()`` loop consumes.  The job
        snapshots ``params``/``weight_version``: the whole batch decodes
        under the one weight version the staleness schedule pinned, even
        if fresher weights arrive while it is parked.
        """
        assert self.params is not None, "weights never synchronized"
        if self.max_new <= 0:
            raise ValueError(f"max_new must be >= 1, got {self.max_new}")
        batch = self.tasks.sample(self.n_prompts, self.n_per_prompt)
        prompts = self._place(jnp.asarray(batch.prompts))
        self.key, sub = jax.random.split(self.key)
        chunk = self.chunk or self.max_new
        n_chunks = -(-self.max_new // chunk)
        with self._on_mesh():
            state = start_rollout(self.params, self.cfg, prompts,
                                  prompts.shape[1] + n_chunks * chunk)
        job = RolloutJob(
            batch_index=self.curr_step if batch_index is None
            else batch_index,
            params=self.params, weight_version=self.weight_version,
            key=sub, meta={"answers": batch.answers},
            max_new=self.max_new, chunk=chunk, n_chunks=n_chunks)
        return job, state

    def begin_batch_pinned(self, batch_index: Optional[int] = None):
        """``begin_batch`` with the params snapshot *pinned* executor-side
        and replaced by a ``PinnedParams`` reference on the job, so a
        remote scheduler round-trips kilobytes of job metadata per chunk
        instead of the weight pytree.  ``emit_batch`` releases the pin;
        a job abandoned before emit must be handed to ``release_job``
        (the scheduler's ``clear``/``drain`` teardown does this) or its
        pin leaks until the executor is torn down."""
        job, state = self.begin_batch(batch_index)
        self._pin_seq += 1
        self._pinned[self._pin_seq] = job.params
        job.params = PinnedParams(self._pin_seq)
        return job, state

    def _job_params(self, job):
        return self._pinned[job.params.key] \
            if isinstance(job.params, PinnedParams) else job.params

    def repin_job(self, job):
        """Re-snapshot an in-flight job's params on the CURRENT weights.

        Supervised re-admission after a respawn: the job's resumable
        ``RolloutState`` survived caller-side, but its admission params
        snapshot (or executor-side pin) died with the process, so the
        job is re-pinned under the replayed -- newest staleness-legal --
        version.  Versions only move forward here; the caller re-asserts
        the bounded-staleness contract on the returned job."""
        assert self.params is not None, \
            "repin before weight replay: respawn must replay weights first"
        assert self.weight_version >= job.weight_version, (
            f"replayed version {self.weight_version} is older than the "
            f"dead worker's admission version {job.weight_version}")
        if isinstance(job.params, PinnedParams):
            self._pinned.pop(job.params.key, None)
            self._pin_seq += 1
            self._pinned[self._pin_seq] = self.params
            job.params = PinnedParams(self._pin_seq)
        else:
            job.params = self.params
        job.weight_version = self.weight_version
        return job

    def release_job(self, job):
        """Release the executor-side resources of a job dropped without
        emitting -- currently just its ``PinnedParams`` snapshot.  Safe
        to call for unpinned jobs (no-op)."""
        params = getattr(job, "params", None)
        if isinstance(params, PinnedParams):
            self._pinned.pop(params.key, None)

    def pinned_count(self) -> int:
        """Live ``PinnedParams`` snapshots (leak-regression probe)."""
        return len(self._pinned)

    def advance_chunk(self, job, state):
        """One resumable ``rollout_chunk`` with the job's key discipline."""
        with self._on_mesh():
            job.key, sub = jax.random.split(job.key)
            state = rollout_chunk(self._job_params(job), self.cfg, state,
                                  sub, n_steps=job.chunk,
                                  temperature=self.temperature)
        job.chunks_done += 1
        return state

    def advance_chunk_rt(self, job, state):
        """``advance_chunk`` returning the (mutated) job alongside the
        state: the round-trip form ``ActorHandle`` routes through so a
        process-backed actor's job mutations (key split, chunk count)
        reach the caller's copy."""
        return job, self.advance_chunk(job, state)

    def emit_batch(self, job, state):
        """Finalize and publish the completed batch."""
        state = finalize_rollout(state, job.max_new)
        out = {
            "tokens": state.tokens,
            "behavior_logp": state.behavior_logp,
            "mask": action_mask(state),
            "prompt_len": state.prompt_len,
            "answers": job.meta["answers"],
            "weight_version": job.weight_version,
        }
        if isinstance(job.params, PinnedParams):
            self._pinned.pop(job.params.key, None)
        self.set_output("completions", out)
        return out

    def emit_batch_snapshot(self, job, state, names):
        """``emit_batch`` + output-port snapshot in one endpoint (the
        remote form: one round-trip, one batch payload)."""
        self.emit_batch(job, state)
        return {n: self.get_output(n) for n in names}

    def step(self):
        job, state = self.begin_batch()
        for _ in range(job.n_chunks):
            state = self.advance_chunk(job, state)
        out = self.emit_batch(job, state)
        self.curr_step += 1
        return out

    # ------------------------------------- continuous-batching engine hooks --
    #
    # The engine (``repro.rl.engine``) lives actor-side: per-round RPCs
    # carry batch indices and finished batches, never KV caches.  The
    # pool worker drives ``engine_enqueue``/``engine_round`` instead of
    # the begin/advance/emit chunk hooks.

    def engine_configure(self, *, max_running_rows: int = 0,
                         row_budgets=None, round_delay_s: float = 0.0,
                         scorer: str = "numeric",
                         leave_one_out: bool = False,
                         kv_layout: str = "", kv_page_size: int = 0,
                         kv_pages: int = 0):
        """(Re)build the in-flight engine.  Called once at worker start
        and again after a respawn (the old engine died with the
        process); any live engine's in-flight work is aborted first.
        A rebuild starts with an empty radix cache in paged mode --
        re-enqueued batches repopulate it on their first admission."""
        from repro.rl.engine import RolloutEngine
        if self._engine is not None:
            self._engine.abort()
        self._engine = RolloutEngine(
            self, max_running_rows=max_running_rows,
            row_budgets=row_budgets, round_delay_s=round_delay_s,
            scorer=scorer, leave_one_out=leave_one_out,
            kv_layout=kv_layout, kv_page_size=kv_page_size,
            kv_pages=kv_pages)

    def engine_enqueue(self, batch_index: int, bound: int = 0) -> int:
        return self._engine.enqueue(batch_index, bound)

    def engine_round(self, names):
        """One engine tick; returns ``(items, idle_rounds)`` where each
        item is the caller-shaped sample-queue entry (batch snapshot
        included -- one round-trip per emitted batch, like
        ``emit_batch_snapshot``)."""
        with self._on_mesh():
            emissions = self._engine.round()
        items = []
        for e in emissions:
            self.set_output("completions", e["out"])
            items.append({
                "batch_index": e["batch_index"],
                "snapshot": {n: self.get_output(n) for n in names},
                "generator": self.name,
                "bound": e["bound"],
                "gen_busy_s": e["busy_s"],
                "gen_idle_s": 0.0,
                "_version": e["weight_version"],
            })
        return items

    def engine_inflight(self):
        return self._engine.inflight_batches()

    def engine_abort(self) -> int:
        return self._engine.abort() if self._engine is not None else 0

    def engine_stats(self):
        return self._engine.snapshot_stats() if self._engine is not None \
            else {}


class RewardExecutor(Executor):
    """Rule-based scorers (lightweight python, as in the paper's Fig. 1)."""

    role = "reward"

    def __init__(self, *, n_per_prompt: int, scorer: str = "numeric",
                 leave_one_out: bool = False, name: str = "reward",
                 mesh=None):
        super().__init__(name, mesh)
        if n_per_prompt < 1:
            raise ValueError(f"n_per_prompt must be >= 1, got {n_per_prompt}")
        if leave_one_out and n_per_prompt < 2:
            raise ValueError(
                "leave_one_out needs n_per_prompt >= 2: the RLOO baseline "
                "averages the other n-1 samples of the group")
        self.n_per_prompt = n_per_prompt
        self.scorer = scorer
        self.leave_one_out = leave_one_out

    @staticmethod
    def _prompt_lens(prompt_len, batch_size: int) -> np.ndarray:
        """Accept a scalar or a per-sequence [B] array of prompt lengths."""
        if np.ndim(prompt_len) == 0:
            return np.full(batch_size, int(prompt_len), dtype=np.int64)
        lens = np.asarray(prompt_len).astype(np.int64).reshape(-1)
        if lens.shape[0] != batch_size:
            raise ValueError(
                f"prompt_len has {lens.shape[0]} entries for a batch of "
                f"{batch_size} sequences")
        return lens

    def step(self):
        comp = self.get_input("completions_with_ref") \
            or self.get_input("completions")
        toks = np.asarray(comp["tokens"])
        plens = self._prompt_lens(comp["prompt_len"], toks.shape[0])
        texts = [rl_data.decode_ids(t[p:]) for t, p in zip(toks, plens)]
        rewards = rl_rewards.score_group(comp["answers"], texts, self.scorer)
        adv = rl_rewards.group_advantages(rewards, self.n_per_prompt,
                                          self.leave_one_out)
        mask = np.asarray(comp["mask"])
        advantages = adv[:, None] * mask
        out = {
            "tokens": comp["tokens"],
            "behavior_logp": comp["behavior_logp"],
            "advantages": jnp.asarray(advantages),
            "mask": comp["mask"],
            "mean_reward": float(rewards.mean()),
        }
        if "ref_logp" in comp:
            out["ref_logp"] = comp["ref_logp"]
        self.set_output("completions_with_reward", out)
        self.curr_step += 1
        return out


class RefPolicyExecutor(Executor):
    """Frozen reference policy pi_base: computes per-token ref logprobs for
    the KL regularization term (paper Sec. 6: reward is often combined with
    lambda_KL * D_KL(pi, pi_base)).  Weights are set once at init from the
    trainer's initial policy and never updated."""

    role = "reference"

    def __init__(self, cfg, *, name: str = "ref", mesh=None):
        super().__init__(name, mesh)
        self.cfg = cfg
        self.params = None
        self._jitted = None

    def set_weights(self, params, version: Optional[int] = None):
        # only the FIRST sync sticks: the reference stays frozen
        if self.params is None:
            self.params = params

    def step(self):
        assert self.params is not None
        comp = self.get_input("completions")
        from repro.models import forward_train

        if self._jitted is None:
            def ref_logp(params, tokens):
                # forward-only scoring: token_logprobs streams vocab tiles
                # through the kernel-dispatch layer, so this path never
                # builds the [B, T, V] fp32 log-softmax the naive gather
                # needs (the ref model shares the trainer's 256k vocab)
                logits, _ = forward_train(params, self.cfg,
                                          {"tokens": tokens})
                lp = token_logprobs(logits[:, :-1], tokens[:, 1:])
                return jnp.pad(lp, ((0, 0), (1, 0)))
            self._jitted = jax.jit(ref_logp)
        out = dict(comp)
        with self._on_mesh():
            out["ref_logp"] = self._jitted(self.params, comp["tokens"])
        self.set_output("completions_with_ref", out)
        self.curr_step += 1
        return out


class TrainerExecutor(Executor):
    """Policy training: AIPO update on scored completions."""

    role = "trainer"

    def __init__(self, cfg, *, lr=1e-3, rho=4.0, clip_mode="aipo",
                 kl_coef=0.0, seed=0, dtype=jnp.float32, mesh=None,
                 name: str = "trainer"):
        super().__init__(name, mesh)
        self.cfg = cfg
        self.state: Optional[TrainState] = None
        self.seed = seed
        self.dtype = dtype
        self._train_step = make_train_step(cfg, lr=lr, rho=rho,
                                           clip_mode=clip_mode,
                                           kl_coef=kl_coef)
        self._jitted = jax.jit(self._train_step)
        self.metrics_history = []

    def init(self):
        # with a submesh, initialize on its first device, then replicate:
        # the same values as an unplaced init, never on another device
        first = None if self.mesh is None else self.mesh.devices.flat[0]
        with jax.default_device(first):
            state = init_train_state(self.cfg, jax.random.PRNGKey(self.seed),
                                     self.dtype)
        self.state = self._place(state)
        self.set_output("policy_model", self.state.params)

    def get_model(self):
        return self.state.params

    def last_metrics(self) -> Dict[str, Any]:
        """The most recent train-step metrics row (RPC-sized: the
        controller records per step without shipping the whole
        ``metrics_history`` across a transport)."""
        return dict(self.metrics_history[-1]) if self.metrics_history \
            else {}

    def recent_metrics(self, n: int):
        """The last ``n`` metrics rows -- the RPC-sized tail for eval
        loops (``metrics_history`` itself grows with the run and would
        cross the transport whole)."""
        return [dict(m) for m in self.metrics_history[-max(0, n):]]

    def step(self):
        with obs_trace.span("step", "trainer"):
            with obs_trace.span("assemble", "trainer"):
                scored = self.get_input("completions_with_reward")
                batch = {
                    "tokens": scored["tokens"],
                    "behavior_logp": scored["behavior_logp"],
                    "advantages": scored["advantages"],
                    "mask": scored["mask"],
                }
                if "ref_logp" in scored:
                    batch["ref_logp"] = scored["ref_logp"]
            with obs_trace.span("dispatch", "trainer"):
                with self._on_mesh():
                    self.state, metrics = self._jitted(self.state, batch)
            with obs_trace.span("readback-wait", "trainer"):
                metrics = {k: float(v) for k, v in metrics.items()}
            if "moe_pairs_held" in metrics:
                from repro.rl.engine import charge_expert_tally, \
                    publish_expert_gauges
                charge_expert_tally(
                    metrics["moe_pairs_held"], metrics["moe_experts_hit"],
                    self.cfg.n_layers - self.cfg.moe.first_k_dense,
                    trainer=True)
                publish_expert_gauges(self.cfg)
            metrics["mean_reward"] = scored.get("mean_reward", 0.0)
            self.metrics_history.append(metrics)
            with obs_trace.span("set-output", "trainer"):
                self.set_output("policy_model", self.state.params)
            self.curr_step += 1
        return metrics

    def save_checkpoint(self, path: str, step: int):
        from repro.train.checkpoint import save_checkpoint
        os.makedirs(path, exist_ok=True)
        save_checkpoint(os.path.join(path, f"{self.name}_{step}"),
                        self.state.params)
