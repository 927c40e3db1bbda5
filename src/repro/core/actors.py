"""Placement-agnostic actor API: one single-controller contract for
thread-, process-, shared-memory- and socket-backed executors (paper
Sec. 5.1, 5.2).

The paper's single-controller architecture has each executor own its
model and submesh while the controller orchestrates them uniformly,
regardless of where they physically run.  This module supplies the
contract that makes placement a deployment knob instead of a code path:

  * ``ActorHandle`` -- what the controller, channels and generator pool
    hold instead of a raw ``Executor``.  Typed endpoints: ``call`` for
    synchronous RPC (``init``/``step``/``get_output``/``emit_batch``...),
    ``cast`` for fire-and-forget sends (``set_weights``), plus
    ``healthy``/``join``/``close`` lifecycle.  ``call`` resolves plain
    attributes too (``handle.call("weight_version")``), so the handle is
    the full executor surface.
  * ``Transport`` -- the pluggable hop under every handle endpoint and
    every ``CommunicationChannel``/``StalenessBuffer`` payload hand-off.
    ``prepare`` stages a channel payload toward the actor's devices
    (resharding ``device_put``/DDMA for in-process submeshes; identity
    for process-backed actors, whose staging *is* the serialization at
    the boundary).

Four transports with identical call/cast/error/close semantics:

  * ``InprocTransport`` -- the executor lives in this process; endpoints
    are direct method calls on the caller's thread.
  * ``ProcTransport`` -- the executor is constructed inside a *spawned*
    subprocess with its own XLA client and GIL; endpoints travel a
    duplex pipe as ``repro.core.wire`` payloads.  Remote exceptions
    re-raise on the caller with the remote traceback attached as
    ``__cause__``; a dead child surfaces as ``ActorDied`` instead of a
    hang; ``close()`` shuts the server down and joins the process.
  * ``ShmTransport`` -- ``ProcTransport`` whose *data plane* is shared
    memory: payloads above a size threshold are scattered straight into
    ``multiprocessing.shared_memory`` ring slots (``wire.serialize_into``
    writes each leaf exactly once, into its final position) while only a
    tiny header crosses the pipe -- the control plane and the weight/
    batch data plane the paper's DDMA separates (Sec. 5.2).  Slots are
    recycled on receiver acks (the reader "releases" a slot only after
    copying out, so a slot being rewritten is never one being read);
    every segment is created -- and on ``close()`` unlinked -- by the
    parent, so a killed child can never leak ``/dev/shm`` entries.
  * ``SocketTransport`` -- the same wire format and server loop over a
    TCP connection, for executors on *independently launched* hosts
    (``python -m repro.launch.train --listen HOST:PORT`` on the remote
    side).  With no address it self-hosts: a local helper process binds
    an ephemeral port and serves exactly one actor -- the localhost
    testing mode.  A dropped connection or killed host surfaces as
    ``ActorDied``.

``DeviceSpec`` gives a child its own device world: for spawned
transports (proc/shm/self-hosted socket) ``device_count`` sets
``XLA_FLAGS`` in the fresh interpreter *before* the backend initializes,
and ``mesh_shape``/``mesh_axes`` build the submesh the executor receives
as its ``mesh=`` kwarg -- so a remote actor pins its own XLA device set
instead of inheriting the controller's.

Ordering guarantee all transports share: operations issued through one
handle are executed in issue order (direct calls trivially; the pipe/
socket is FIFO and the server single-threaded), so
``cast("set_weights", ...)`` followed by ``call("weight_version")``
always observes the cast.

``spawn_actor(factory, *args, transport=..., **kwargs)`` builds an
executor behind a handle; ``transport=None`` reads ``REPRO_TRANSPORT``
(default ``inproc``), which is how the test suites and launcher flip an
entire pipeline between placements without touching wiring code.
"""
from __future__ import annotations

import collections
import logging
import multiprocessing as mp
import os
import pickle
import socket as socketlib
import struct
import threading
import time
import traceback
import weakref
from dataclasses import dataclass
from multiprocessing import shared_memory
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import ddma
from repro.core import wire
from repro.obs import trace as obs_trace

_log = logging.getLogger(__name__)

#: max events per piggybacked ``("__trace__", events)`` frame, so a
#: long-buffering child never turns one reply into a giant frame
_TRACE_FLUSH_BATCH = 512


class ActorDied(RuntimeError):
    """The process/host backing an actor exited (or was killed, or its
    connection dropped): the handle fails fast instead of blocking on a
    channel nobody will ever write."""


class RemoteActorError(RuntimeError):
    """Carries a remote traceback.  When the remote exception itself is
    picklable it re-raises as its original type with this as its
    ``__cause__``; otherwise this is the raised error."""


def _pack_exc(e: BaseException) -> Tuple[Optional[bytes], str]:
    tb = "".join(traceback.format_exception(type(e), e, e.__traceback__))
    try:
        blob = pickle.dumps(e)
    except Exception:
        blob = None
    return blob, tb


def _unpack_exc(payload, actor: str) -> BaseException:
    blob, tb = payload
    cause = RemoteActorError(
        f"remote traceback from actor '{actor}':\n{tb}")
    if blob is not None:
        try:
            exc = pickle.loads(blob)
        except Exception:
            exc = None
        if isinstance(exc, BaseException):
            exc.__cause__ = cause
            return exc
    return cause


# ------------------------------------------------------------ device specs --

@dataclass(frozen=True)
class DeviceSpec:
    """Per-child device/mesh request.

    ``device_count`` > 0 asks the child process for that many emulated
    host devices (``--xla_force_host_platform_device_count``; applied in
    the fresh interpreter before the XLA backend initializes -- only
    meaningful for spawned children, a ``--listen`` host pins its own
    device set at launch).  ``mesh_shape``/``mesh_axes`` build the mesh
    the executor receives as its ``mesh=`` kwarg from *its own* device
    world."""
    device_count: int = 0
    mesh_shape: Tuple[int, ...] = ()
    mesh_axes: Tuple[str, ...] = ("data", "model")

    def apply_env(self):
        if self.device_count > 0:
            import re
            # replace any inherited device-count flag (a substring or
            # last-flag-wins heuristic would let a parent's count
            # silently override the spec's)
            cur = re.sub(r"--xla_force_host_platform_device_count=\d+",
                         "", os.environ.get("XLA_FLAGS", ""))
            flag = ("--xla_force_host_platform_device_count="
                    f"{self.device_count}")
            os.environ["XLA_FLAGS"] = " ".join((cur + " " + flag).split())

    def build_mesh(self):
        if not self.mesh_shape:
            return None
        from repro.launch.mesh import make_mesh
        return make_mesh(self.mesh_shape, self.mesh_axes)


# --------------------------------------------------------------- transports --

def _describe_executor(ex, fallback_name: str) -> Dict[str, Any]:
    """The actor identity/capability surface, computed next to the
    executor (in-process or child-side) -- one definition, so inproc and
    proc handles can never disagree about a capability flag."""
    return {"name": getattr(ex, "name", fallback_name),
            "role": getattr(ex, "role", "generic"),
            "chunk_hooks": hasattr(ex, "begin_batch"),
            "pinned_hooks": hasattr(ex, "begin_batch_pinned"),
            "engine_hooks": hasattr(ex, "engine_round"),
            "staged_weights": hasattr(ex, "stage_weights")
            and hasattr(ex, "set_weights")}


def _invoke(ex, method: str, args, kwargs):
    """Endpoint dispatch: a callable attribute is invoked, a plain
    attribute is read (args rejected) -- shared by all transports."""
    attr = getattr(ex, method)
    if callable(attr):
        return attr(*args, **(kwargs or {}))
    assert not args and not kwargs, \
        f"'{method}' is an attribute, not an endpoint"
    return attr

def _payload_sharding(mesh, comm_type, x):
    from repro.core.channels import CommType   # circular at import time only
    if mesh is None:
        return None
    if comm_type == CommType.SCATTER and hasattr(x, "ndim") and x.ndim >= 1:
        axes = mesh.axis_names
        return NamedSharding(mesh, P(axes[0]))
    return NamedSharding(mesh, P())            # replicated


class Transport:
    """Strategy hosting one actor and carrying its endpoints.

    ``describe()`` returns static identity (``name``/``role``/
    ``chunk_hooks``); ``mesh`` is the live submesh for in-process actors
    (None for process-backed ones -- their mesh lives with them);
    ``prepare`` stages a channel payload toward the actor's devices."""

    def describe(self) -> Dict[str, Any]:
        raise NotImplementedError

    #: True when endpoints cross a process boundary (payloads serialized)
    remote: bool = False

    @property
    def mesh(self):
        return None

    def call(self, method: str, args=(), kwargs=None,
             timeout: Optional[float] = None):
        raise NotImplementedError

    def cast(self, method: str, args=(), kwargs=None):
        raise NotImplementedError

    def prepare(self, data, comm_type):
        return data

    def drain_trace(self) -> int:
        """Pull buffered remote trace events (0 for in-process actors,
        whose events land in the shared tracer directly)."""
        return 0

    def healthy(self) -> bool:
        return True

    def join(self, timeout: Optional[float] = None):
        pass

    def close(self):
        pass


class InprocTransport(Transport):
    """The executor lives in this process; endpoints are direct method
    calls on the caller's thread -- today's threaded controller, behind
    the placement-agnostic contract."""

    def __init__(self, executor):
        self.executor = executor

    def describe(self):
        return _describe_executor(self.executor,
                                  type(self.executor).__name__)

    @property
    def mesh(self):
        return getattr(self.executor, "mesh", None)

    def call(self, method, args=(), kwargs=None, timeout=None):
        return _invoke(self.executor, method, args, kwargs)

    def cast(self, method, args=(), kwargs=None):
        self.call(method, args, kwargs)

    def prepare(self, data, comm_type):
        """Stage a channel payload onto this actor's submesh: DDMA/PS
        reshard for weight payloads, resharding ``device_put`` for data
        (the ICI/DCN zero-copy path); no-ops without a mesh."""
        from repro.core.channels import CommType   # lazy: import cycle
        mesh = self.mesh
        if comm_type.is_weights:
            if mesh is not None:
                sharding = NamedSharding(mesh, P())
                sync = (ddma.ddma_weight_sync
                        if comm_type == CommType.DDMA_WEIGHTS_UPDATE
                        else ddma.ps_weight_sync)
                data = sync(data, sharding)
            return data
        if mesh is not None:
            data = jax.tree.map(
                lambda x: jax.device_put(
                    x, _payload_sharding(mesh, comm_type, x))
                if isinstance(x, (jax.Array, jnp.ndarray)) else x,
                data)
        return data


# ----------------------------------------------------- shared-memory plane --
#
# The shm data plane moves any wire payload above a size threshold through
# ring slots in /dev/shm while only a tiny header crosses the pipe.  Frames
# on the pipe are tagged:
#
#   0x00 + wire bytes                      inline message (small payloads)
#   0x01 + pickle((slot, seg_name, n))     message lives in a shm slot
#   0x02 + pickle([slot, ...])             receiver acks consumed slots
#
# Each direction has its own ring.  The parent *creates every segment* in
# both rings (the child only attaches), so ``close()`` can unlink them all
# even after a SIGKILLed child -- the no-orphaned-segments guarantee.  A
# slot is released only when the receiver acks it after copying the
# payload out (``wire.deserialize`` retains no views), which is what makes
# slot reuse safe: a slot being rewritten is never one being read.

_SHM_REGISTRY: Dict[str, shared_memory.SharedMemory] = {}
_SHM_REGISTRY_LOCK = threading.Lock()

SHM_THRESHOLD_DEFAULT = 1 << 16          # 64 KiB
SHM_SLOTS_DEFAULT = 4
SHM_SLOT_BYTES_DEFAULT = 32 << 20        # fixed child->parent slot size


class _RingFull(Exception):
    """No free slot right now: the sender must pump acks and retry."""


def _shm_create(size: int) -> shared_memory.SharedMemory:
    seg = shared_memory.SharedMemory(create=True, size=size)
    with _SHM_REGISTRY_LOCK:
        _SHM_REGISTRY[seg.name] = seg
    return seg


def _shm_unlink(seg: shared_memory.SharedMemory):
    with _SHM_REGISTRY_LOCK:
        _SHM_REGISTRY.pop(seg.name, None)
    try:
        seg.close()
    except BufferError:     # pragma: no cover - a view outlived the codec
        pass
    try:
        seg.unlink()
    except FileNotFoundError:    # pragma: no cover - already gone
        pass


def _shm_attach(name: str) -> shared_memory.SharedMemory:
    """Attach to a parent-created segment without re-registering it with
    the (shared) resource tracker -- on 3.10 attaching registers the
    segment a second time, and any unregister then strips the *parent's*
    registration, so suppress registration entirely for the attach (the
    3.13 ``track=False`` semantics)."""
    from multiprocessing import resource_tracker
    orig = resource_tracker.register
    resource_tracker.register = lambda *a, **k: None
    try:
        return shared_memory.SharedMemory(name=name)
    finally:
        resource_tracker.register = orig


class _ShmRing:
    """Sender-side slot allocator over a ring of shm segments.

    ``grow=True`` (parent->child): slots are created/replaced on demand
    to fit the payload, always by the parent.  ``grow=False``
    (child->parent): the parent pre-created fixed-size segments at spawn
    and the child merely attaches; payloads that cannot ever fit fall
    back to inline pipe frames."""

    def __init__(self, n_slots: int, *, grow: bool, min_bytes: int,
                 segments: Optional[List[shared_memory.SharedMemory]] = None):
        self._grow = grow
        self._min_bytes = max(1, min_bytes)
        self._lock = threading.Lock()
        self._slots: List[Optional[shared_memory.SharedMemory]] = \
            list(segments) if segments is not None else [None] * n_slots
        self._views = [memoryview(s.buf) if s is not None else None
                       for s in self._slots]
        self._free = [True] * len(self._slots)
        self.created: List[shared_memory.SharedMemory] = []

    def try_acquire(self, nbytes: int):
        """(slot_idx, writable view, segment name) or None (ring full)."""
        with self._lock:
            for i, seg in enumerate(self._slots):
                if seg is not None and self._free[i] and seg.size >= nbytes:
                    self._free[i] = False
                    return i, self._views[i], seg.name
            if not self._grow:
                return None
            for i, seg in enumerate(self._slots):
                if self._free[i]:
                    if seg is not None:
                        self._views[i].release()
                        _shm_unlink(seg)
                    seg = _shm_create(max(nbytes, self._min_bytes))
                    self.created.append(seg)
                    self._slots[i] = seg
                    self._views[i] = memoryview(seg.buf)
                    self._free[i] = False
                    return i, self._views[i], seg.name
            return None

    def can_fit(self, nbytes: int) -> bool:
        if self._grow:
            return True
        with self._lock:
            return any(s is not None and s.size >= nbytes
                       for s in self._slots)

    def release(self, idx: int):
        with self._lock:
            self._free[idx] = True

    def close(self):
        with self._lock:
            for v in self._views:
                if v is not None:
                    v.release()
            self._views = [None] * len(self._slots)


class _PlainCodec:
    """Frames are raw wire bytes; nothing rides shared memory.

    Encoding is split in two so ring-full retries never redo the
    expensive part: ``prepare`` runs the flatten/serialize work once,
    ``encode_prepared`` turns it into the frame (and is the only step a
    ``_RingFull`` retry repeats)."""

    def prepare(self, obj):
        return wire.serialize(obj)

    def encode_prepared(self, prep) -> bytes:
        return prep

    def decode(self, frame):
        return "msg", wire.deserialize(frame), None

    def close(self):
        pass


class _ShmCodec:
    """Tagged frames; payloads >= threshold ride ``tx`` ring slots.

    ``rx_fixed`` maps segment names this side may receive payloads in to
    pre-opened segments (the parent's view of the child-tx ring);
    anything else is attached on first reference (the child's view of
    the parent's growable ring) and re-attached when a slot's segment is
    replaced by a larger one."""

    def __init__(self, tx: Optional[_ShmRing], threshold: int, *,
                 rx_fixed: Optional[Dict[str, shared_memory.SharedMemory]]
                 = None, attach_rx: bool = False):
        self.tx = tx
        self.threshold = max(1, threshold)
        self._attach_rx = attach_rx
        self._rx: Dict[int, tuple] = {}       # slot idx -> (name, seg, view)
        self._rx_fixed = dict(rx_fixed or {})
        self._rx_fixed_views: Dict[str, memoryview] = {}

    def prepare(self, obj):
        """One flatten pass (device->host for jax leaves): inline frames
        are fully serialized here; ring-bound payloads stay ``Planned``
        so a ``_RingFull`` retry repeats only the slot acquisition."""
        planned = wire.plan(obj)
        if self.tx is None or planned.size < self.threshold or \
                not self.tx.can_fit(planned.size):
            return b"\x00" + wire.serialize(planned)
        return planned

    def encode_prepared(self, prep) -> bytes:
        if not isinstance(prep, wire.Planned):
            return prep
        got = self.tx.try_acquire(prep.size)
        if got is None:
            raise _RingFull
        idx, view, name = got
        wire.serialize_into(prep, view)
        return b"\x01" + pickle.dumps((idx, name, prep.size))

    def decode(self, frame):
        """(kind, payload, ack_frame_to_send_or_None)."""
        tag = frame[0]
        body = memoryview(frame)[1:]
        if tag == 0:
            return "msg", wire.deserialize(body), None
        if tag == 2:
            for idx in pickle.loads(body):
                self.tx.release(idx)
            return "ack", None, None
        assert tag == 1, f"bad frame tag {tag}"
        idx, name, nbytes = pickle.loads(body)
        view = self._rx_view(idx, name)
        # copy_arrays: the slot is recycled the moment we ack it, and
        # jnp.asarray would otherwise zero-copy-alias the mapping
        obj = wire.deserialize(view[:nbytes], copy_arrays=True)
        # the payload is fully copied out: hand the ack back for the
        # conn owner to send, releasing the slot for reuse
        return "msg", obj, b"\x02" + pickle.dumps([idx])

    def _rx_view(self, idx: int, name: str) -> memoryview:
        if name in self._rx_fixed:
            view = self._rx_fixed_views.get(name)
            if view is None:
                view = self._rx_fixed_views[name] = \
                    memoryview(self._rx_fixed[name].buf)
            return view
        cur = self._rx.get(idx)
        if cur is None or cur[0] != name:     # slot segment was replaced
            if cur is not None:
                cur[2].release()
                cur[1].close()
            assert self._attach_rx, f"unknown shm segment {name!r}"
            seg = _shm_attach(name)
            cur = (name, seg, memoryview(seg.buf))
            self._rx[idx] = cur
        return cur[2]

    def close(self):
        for name, seg, view in self._rx.values():
            view.release()
            seg.close()
        self._rx.clear()
        for view in self._rx_fixed_views.values():
            view.release()
        self._rx_fixed_views.clear()
        if self.tx is not None:
            self.tx.close()


def _make_child_codec(boot: Dict[str, Any]):
    shm_boot = boot.get("shm")
    if not shm_boot:
        return _PlainCodec()
    segs = [_shm_attach(n) for n in shm_boot["child_tx_names"]]
    ring = _ShmRing(len(segs), grow=False, min_bytes=1, segments=segs)
    return _ShmCodec(ring, shm_boot["threshold"], attach_rx=True)


# -------------------------------------------------------------- the server --
# Child-side server: one message loop, one executor, FIFO execution.
# Runs in a *spawned* interpreter (or a --listen host), so it owns its
# own XLA client and GIL.

def _actor_server(conn, factory, args, kwargs, boot=None):
    boot = boot or {}
    spec: Optional[DeviceSpec] = boot.get("device_spec")
    if spec is not None and boot.get("apply_device_env"):
        # fresh interpreter: the XLA backend has not initialized yet, so
        # the flag still takes effect at first device use
        spec.apply_env()
    if boot.get("trace"):
        # programmatic enable (no REPRO_TRACE in this interpreter's env,
        # e.g. a --listen host): join the parent's tracing session
        obs_trace.enable()
    codec = _make_child_codec(boot)
    pending: collections.deque = collections.deque()

    def pump_once(block: bool) -> bool:
        """Read one frame; acks release tx slots, messages queue."""
        if not block and not conn.poll(0):
            return False
        kind, obj, ack = codec.decode(conn.recv_bytes())
        if ack is not None:
            conn.send_bytes(ack)
        if kind == "msg":
            pending.append(obj)
        return True

    def send_obj(obj):
        prep = codec.prepare(obj)
        while True:
            try:
                frame = codec.encode_prepared(prep)
                break
            except _RingFull:
                # the parent is draining our replies (and acking) --
                # block until an ack frees a slot
                pump_once(block=True)
        conn.send_bytes(frame)

    def next_msg():
        while not pending:
            pump_once(block=True)
        return pending.popleft()

    def flush_trace():
        """Ship buffered child events to the parent as ``__trace__``
        frames (piggybacked just before a reply, so the parent's
        ``_recv`` absorbs them while draining for that reply)."""
        t = obs_trace.tracer()
        if t is None:
            return
        evs = t.drain()
        while evs:
            send_obj(("__trace__", evs[:_TRACE_FLUSH_BATCH]))
            evs = evs[_TRACE_FLUSH_BATCH:]

    try:
        try:
            if spec is not None and spec.mesh_shape and \
                    "mesh" not in (kwargs or {}):
                kwargs = dict(kwargs or {})
                kwargs["mesh"] = spec.build_mesh()
            ex = factory(*args, **(kwargs or {}))
            desc = _describe_executor(ex, getattr(factory, "__name__", "?"))
            if obs_trace.enabled():
                # the tracer's process label is the actor name: one pid
                # row per actor in the exported timeline
                obs_trace.enable(desc["name"])
            send_obj(("hello", desc))
        except BaseException as e:
            send_obj(("hello_err", _pack_exc(e)))
            return
        while True:
            try:
                msg = next_msg()
            except (EOFError, OSError):
                return                       # parent went away
            # tracing parents append a flow-context element; untraced
            # ones send the original 5-tuple
            seq, kind, method, cargs, ckw, *rest = msg
            if kind == "trace_sync":
                # clock-offset handshake: answer with our trace clock
                # immediately (no flush -- the round trip must stay
                # minimal, its RTT bounds the offset error)
                send_obj((seq, "ok", obs_trace.now()))
                continue
            if kind == "drain_trace":
                t = obs_trace.tracer()
                send_obj((seq, "ok", t.drain() if t is not None else []))
                continue
            if kind == "shutdown":
                flush_trace()                # final drain rides the ack
                send_obj((seq, "ok", None))
                return
            try:
                t = obs_trace.tracer()
                if t is None:
                    result = _invoke(ex, method, cargs, ckw)
                else:
                    with t.span(f"serve:{method}", "rpc"):
                        if rest and rest[0]:
                            t.flow_end(rest[0])
                        result = _invoke(ex, method, cargs, ckw)
                if kind == "call":
                    flush_trace()
                    send_obj((seq, "ok", result))
            except BaseException as e:
                # call errors answer the caller; cast errors surface on
                # the next call through this handle (FIFO, status-first)
                flush_trace()
                send_obj((seq, "err", _pack_exc(e)))
    except (EOFError, OSError, BrokenPipeError):
        return                               # peer vanished mid-reply
    finally:
        codec.close()


_LIVE_TRANSPORTS: "weakref.WeakSet[_RpcTransport]" = weakref.WeakSet()


class _RpcTransport(Transport):
    """Shared RPC machinery over a duplex byte connection + codec.

    A per-handle lock serializes request/response pairs, so replies
    match requests without a reader thread; liveness is polled while
    waiting, so a dead peer raises ``ActorDied`` within ~100ms instead
    of hanging until the deadline.  Subclasses supply the connection,
    the codec, peer liveness and teardown."""

    _POLL_S = 0.1
    remote = True

    def _init_rpc(self, conn, codec, call_timeout: float):
        self._conn = conn
        self._codec = codec
        self._lock = threading.RLock()
        self._seq = 0
        self._abandoned: set = set()     # seqs whose caller timed out
        self._stash: collections.deque = collections.deque()
        self._closed = False
        self.call_timeout = call_timeout
        self.on_death = None             # liveness hook: cb(ActorDied)
        self._death_notified = False
        self._trace_offset = 0.0         # child clock -> our trace epoch
        _LIVE_TRANSPORTS.add(self)

    # ------------------------------------------------------------ plumbing --

    def describe(self):
        return dict(self._desc)

    @property
    def name(self):
        return getattr(self, "_desc", {}).get("name", "?")

    def _peer_alive(self) -> bool:
        raise NotImplementedError

    def _exit_desc(self) -> str:
        raise NotImplementedError

    def _died(self, what) -> ActorDied:
        self._closed = True
        err = ActorDied(
            f"actor '{self.name}' {self._exit_desc()} during {what}")
        cb, self.on_death = self.on_death, None
        if cb is not None and not self._death_notified:
            self._death_notified = True
            try:
                cb(err)
            except Exception:                # pragma: no cover - diagnostics
                _log.exception("on_death callback for '%s'", self.name)
        return err

    def _decode_frame(self, frame, what):
        """One decoded frame: acks are internal, messages come back."""
        t = obs_trace.tracer()
        if t is None:
            kind, obj, ack = self._codec.decode(frame)
        else:
            with t.span("deserialize", "wire", actor=self.name,
                        bytes=len(frame)):
                kind, obj, ack = self._codec.decode(frame)
        if ack is not None:
            try:
                self._conn.send_bytes(ack)
            except (BrokenPipeError, OSError):
                raise self._died(what)
        return kind, obj

    def _absorb_if_trace(self, obj) -> bool:
        """Intercept a piggybacked ``("__trace__", events)`` frame:
        absorb the child's events (clock-offset corrected) instead of
        handing it to a caller expecting a reply."""
        if isinstance(obj, tuple) and len(obj) == 2 and \
                obj[0] == "__trace__":
            obs_trace.absorb(obj[1], self._trace_offset)
            return True
        return False

    def _recv(self, timeout, what):
        """One message, polling peer liveness while waiting."""
        if self._stash:
            return self._stash.popleft()
        deadline = time.monotonic() + (timeout if timeout is not None
                                       else self.call_timeout)
        while True:
            try:
                if self._conn.poll(self._POLL_S):
                    kind, obj = self._decode_frame(
                        self._conn.recv_bytes(), what)
                    if kind == "msg" and not self._absorb_if_trace(obj):
                        return obj
                    continue
            except (EOFError, OSError):
                raise self._died(what)
            if not self._peer_alive():
                # drain a reply that raced the exit before declaring
                # death
                try:
                    while self._conn.poll(0):
                        kind, obj = self._decode_frame(
                            self._conn.recv_bytes(), what)
                        if kind == "msg" and not self._absorb_if_trace(obj):
                            return obj
                except (EOFError, OSError) as e:
                    # expected when the peer died mid-write; log so a
                    # torn frame is distinguishable from a clean exit
                    _log.debug("actor '%s': connection drained after peer "
                               "exit during %s: %r", self.name, what, e)
                raise self._died(what)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"actor '{self.name}' gave no reply to {what} within "
                    f"{timeout if timeout is not None else self.call_timeout}"
                    f"s (peer still alive)")

    def _encode(self, msg, deadline, what):
        """(frame, payload bytes); retries slot acquisition on a full
        shm ring without redoing the serialize work."""
        prep = self._codec.prepare(msg)
        nbytes = prep.size if isinstance(prep, wire.Planned) else len(prep)
        while True:
            try:
                return self._codec.encode_prepared(prep), nbytes
            except _RingFull:
                # every slot is in flight: pump the connection until the
                # receiver acks one (replies read here are stashed for
                # the pending _recv)
                self._pump_frame(deadline, f"shm ack for {what}")

    def _send(self, msg, what):
        deadline = time.monotonic() + self.call_timeout
        t = obs_trace.tracer()
        if t is None:
            frame, _ = self._encode(msg, deadline, what)
            try:
                self._conn.send_bytes(frame)
            except (BrokenPipeError, OSError):
                raise self._died(what)
            return
        with t.span("serialize", "wire", actor=self.name) as sp:
            frame, nbytes = self._encode(msg, deadline, what)
            sp.set(bytes=nbytes)
        with t.span("transfer", "wire", actor=self.name, bytes=nbytes):
            try:
                self._conn.send_bytes(frame)
            except (BrokenPipeError, OSError):
                raise self._died(what)

    def _pump_frame(self, deadline, what):
        """Process exactly one incoming frame: acks release tx slots
        (the codec's decode side effect), replies are stashed for the
        ``_recv`` that is waiting on them."""
        while True:
            try:
                if self._conn.poll(self._POLL_S):
                    kind, obj = self._decode_frame(
                        self._conn.recv_bytes(), what)
                    if kind == "msg" and not self._absorb_if_trace(obj):
                        self._stash.append(obj)
                    return
            except (EOFError, OSError):
                raise self._died(what)
            if not self._peer_alive():
                raise self._died(what)
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"actor '{self.name}': no {what} within "
                    f"{self.call_timeout}s")

    # ----------------------------------------------------------- endpoints --

    def call(self, method, args=(), kwargs=None, timeout=None):
        if self._closed:
            raise ActorDied(f"actor '{self.name}' is closed")
        t = obs_trace.tracer()
        sp = obs_trace.NOOP_SPAN if t is None \
            else t.span(f"rpc:{method}", "rpc", actor=self.name)
        with sp:
            # when tracing, the flow id rides the frame as a 6th element
            # (the child's serve span binds it: the caller->callee arrow);
            # untraced messages keep the original 5-tuple byte-for-byte
            fid = t.flow_start() if t is not None else None
            with self._lock:
                seq = self._seq
                self._seq += 1
                msg = (seq, "call", method, tuple(args), kwargs or {})
                self._send(msg if fid is None else msg + (fid,),
                           what=f"call '{method}'")
                try:
                    rseq, status, payload = self._reply_for(
                        seq, timeout, what=f"call '{method}'")
                except TimeoutError:
                    # the child may still answer later: remember to
                    # discard that late reply so it is never handed to
                    # the next call
                    self._abandoned.add(seq)
                    raise
        if status == "err":
            raise _unpack_exc(payload, self.name)
        return payload

    def _reply_for(self, seq, timeout, what):
        """The reply matching ``seq``, draining stale replies on the way.

        Legitimate stale replies are (a) a failed *cast*'s error notice
        (casts are silent on success) -- surfaced as this call's error,
        but only after this call's own reply has been consumed, else the
        next caller would read it (pipe desync) -- and (b) the late
        reply to a call whose caller already timed out, which is
        discarded."""
        cast_error = None
        while True:
            rseq, status, payload = self._recv(timeout, what=what)
            if rseq == seq:
                if cast_error is not None:   # FIFO: the cast failed first
                    return rseq, "err", cast_error
                return rseq, status, payload
            if rseq in self._abandoned:      # timed-out call's late reply
                self._abandoned.discard(rseq)
                continue
            if status == "err" and rseq < seq:
                if cast_error is None:
                    cast_error = payload
                continue
            raise AssertionError(
                f"actor '{self.name}': unexpected stale reply "
                f"{rseq}/{status!r} while waiting for {seq}")

    def cast(self, method, args=(), kwargs=None):
        if self._closed:
            raise ActorDied(f"actor '{self.name}' is closed")
        t = obs_trace.tracer()
        sp = obs_trace.NOOP_SPAN if t is None \
            else t.span(f"cast:{method}", "rpc", actor=self.name)
        with sp:
            fid = t.flow_start() if t is not None else None
            with self._lock:
                seq = self._seq
                self._seq += 1
                msg = (seq, "cast", method, tuple(args), kwargs or {})
                self._send(msg if fid is None else msg + (fid,),
                           what=f"cast '{method}'")

    # --------------------------------------------------------------- trace --

    def _clock_sync(self, rounds: int = 3):
        """Clock-offset handshake at spawn: best-of-N ``trace_sync``
        round trips, keeping the offset from the lowest-RTT round
        (midpoint estimate: child clock + offset == our trace epoch).
        Absorbed child events are shifted by it, putting every process
        on one exported timeline.  No-op unless tracing is enabled."""
        t = obs_trace.tracer()
        if t is None:
            return
        best_rtt = None
        for _ in range(max(1, rounds)):
            with self._lock:
                seq = self._seq
                self._seq += 1
                t0 = obs_trace.now()
                self._send((seq, "trace_sync", "", (), {}),
                           what="trace_sync")
                _, status, child_t = self._reply_for(
                    seq, 10.0, what="trace_sync")
            t1 = obs_trace.now()
            if status != "ok":               # pragma: no cover - old peer
                return
            rtt = t1 - t0
            if best_rtt is None or rtt < best_rtt:
                best_rtt = rtt
                self._trace_offset = (t0 + t1) / 2.0 - child_t
        t.instant(f"clock-sync:{self.name}", "rpc",
                  offset_s=self._trace_offset, rtt_s=best_rtt)

    def drain_trace(self) -> int:
        """Pull the child's buffered trace events now (the piggyback
        path drains on every call reply; this is the explicit flush for
        quiet children).  Returns the number of events absorbed."""
        t = obs_trace.tracer()
        if t is None or self._closed:
            return 0
        with self._lock:
            seq = self._seq
            self._seq += 1
            self._send((seq, "drain_trace", "", (), {}),
                       what="drain_trace")
            _, status, payload = self._reply_for(
                seq, None, what="drain_trace")
        if status != "ok":                   # pragma: no cover - old peer
            return 0
        obs_trace.absorb(payload, self._trace_offset)
        return len(payload)

    def healthy(self) -> bool:
        return not self._closed and self._peer_alive()

    def close(self):
        """Graceful shutdown -> teardown.  Idempotent."""
        if self._closed:
            self._teardown()
            return
        self._closed = True
        try:
            with self._lock:
                seq = self._seq
                self._seq += 1
                self._send((seq, "shutdown", "", (), {}),
                           what="shutdown")
                self._reply_for(seq, 10.0, what="shutdown ack")
        except (ActorDied, TimeoutError, OSError, AssertionError) as e:
            # graceful shutdown is best-effort (the peer may already be
            # gone), but an unacked shutdown is worth a trace when
            # debugging teardown hangs
            _log.debug("actor '%s': graceful shutdown not acknowledged "
                       "(%s: %s); proceeding to teardown",
                       self.name, type(e).__name__, e)
        self._teardown()

    def _teardown(self):
        raise NotImplementedError


class ProcTransport(_RpcTransport):
    """Hosts the executor in a spawned subprocess with its own XLA client.

    The factory and its arguments are shipped to the child (spawn
    semantics: fresh interpreter, no inherited XLA state), the executor
    is constructed there, and every endpoint travels the duplex pipe as
    a ``wire`` payload.  ``device_spec`` gives the child its own device
    count and submesh (applied before its backend initializes)."""

    def __init__(self, factory, args=(), kwargs=None, *,
                 spawn_timeout: float = 180.0, call_timeout: float = 600.0,
                 device_spec: Optional[DeviceSpec] = None):
        self._ctx = mp.get_context("spawn")
        self._conn_parent, child_conn = self._ctx.Pipe(duplex=True)
        boot = self._make_boot(device_spec)
        self._proc = self._ctx.Process(
            target=_actor_server,
            args=(child_conn, factory, args, kwargs or {}, boot),
            daemon=True, name=f"actor-{getattr(factory, '__name__', '?')}")
        self._init_rpc(self._conn_parent, self._make_codec(), call_timeout)
        self._proc.start()
        child_conn.close()                   # parent keeps one end only
        status, payload = self._recv(spawn_timeout, what="actor handshake")
        if status == "hello_err":
            self._teardown()
            raise _unpack_exc(payload, getattr(factory, "__name__", "?"))
        assert status == "hello", f"bad handshake: {status!r}"
        self._desc = payload
        self._clock_sync()

    def _make_boot(self, device_spec) -> Dict[str, Any]:
        return {"device_spec": device_spec, "apply_device_env": True,
                "trace": obs_trace.enabled()}

    def _make_codec(self):
        return _PlainCodec()

    def _peer_alive(self) -> bool:
        return self._proc.is_alive()

    def _exit_desc(self) -> str:
        return (f"process (pid {self._proc.pid}) exited with code "
                f"{self._proc.exitcode}")

    def join(self, timeout: Optional[float] = None):
        self._proc.join(timeout)

    def _teardown(self):
        if self._proc.is_alive():
            self._proc.join(timeout=5.0)
        if self._proc.is_alive():
            self._proc.terminate()
            self._proc.join(timeout=5.0)
        if self._proc.is_alive():            # pragma: no cover - last resort
            self._proc.kill()
            self._proc.join(timeout=5.0)
        self._codec.close()
        self._conn.close()


class ShmTransport(ProcTransport):
    """``ProcTransport`` with a shared-memory data plane.

    Control messages stay on the pipe; any payload whose serialized size
    reaches ``threshold`` is scattered into a shm ring slot instead
    (``wire.serialize_into``: one copy per leaf, straight into the
    mapping) and only ``(slot, segment, nbytes)`` crosses the pipe.  The
    parent->child ring grows its slots to fit (weights); the
    child->parent ring is ``slots`` pre-created fixed segments of
    ``slot_bytes`` (batches), with oversized replies falling back to
    inline frames.  All segments are parent-created and parent-unlinked:
    ``close()`` leaves nothing in /dev/shm even if the child was
    SIGKILLed mid-transfer."""

    def __init__(self, factory, args=(), kwargs=None, *,
                 spawn_timeout: float = 180.0, call_timeout: float = 600.0,
                 device_spec: Optional[DeviceSpec] = None,
                 threshold: Optional[int] = None,
                 slots: Optional[int] = None,
                 slot_bytes: Optional[int] = None):
        self._threshold = threshold if threshold is not None else int(
            os.environ.get("REPRO_SHM_THRESHOLD", SHM_THRESHOLD_DEFAULT))
        n_slots = slots if slots is not None else int(
            os.environ.get("REPRO_SHM_SLOTS", SHM_SLOTS_DEFAULT))
        child_bytes = slot_bytes if slot_bytes is not None else int(
            os.environ.get("REPRO_SHM_SLOT_BYTES", SHM_SLOT_BYTES_DEFAULT))
        # child->parent segments exist before the child does; the child
        # only ever attaches, so ownership (and unlink duty) stays here
        self._child_tx_segs = [_shm_create(child_bytes)
                               for _ in range(max(2, n_slots // 2))]
        self._tx_ring = _ShmRing(max(2, n_slots), grow=True,
                                 min_bytes=self._threshold * 4)
        super().__init__(factory, args, kwargs, spawn_timeout=spawn_timeout,
                         call_timeout=call_timeout, device_spec=device_spec)

    def _make_boot(self, device_spec) -> Dict[str, Any]:
        boot = super()._make_boot(device_spec)
        boot["shm"] = {
            "child_tx_names": [s.name for s in self._child_tx_segs],
            "threshold": self._threshold,
        }
        return boot

    def _make_codec(self):
        return _ShmCodec(self._tx_ring, self._threshold,
                         rx_fixed={s.name: s for s in self._child_tx_segs})

    def segment_names(self) -> List[str]:
        """Every live segment this transport owns (tests/leak checks)."""
        return ([s.name for s in self._child_tx_segs] +
                [s.name for s in self._tx_ring.created
                 if s.name in _SHM_REGISTRY])

    def _teardown(self):
        super()._teardown()                  # joins child, closes codec
        for seg in self._child_tx_segs + self._tx_ring.created:
            _shm_unlink(seg)


# ------------------------------------------------------------ socket plane --

_FRAME = struct.Struct(">Q")


class _SockConn:
    """Length-prefixed frames over a TCP socket, with the same
    ``send_bytes``/``recv_bytes``/``poll``/``close`` surface as an
    ``mp.Pipe`` connection, so the server loop and RPC machinery are
    transport-agnostic."""

    def __init__(self, sock: socketlib.socket):
        sock.setsockopt(socketlib.IPPROTO_TCP, socketlib.TCP_NODELAY, 1)
        sock.settimeout(None)
        self._sock = sock

    def send_bytes(self, data):
        try:
            self._sock.sendall(_FRAME.pack(len(data)))
            self._sock.sendall(data)
        except (BrokenPipeError, ConnectionError, OSError) as e:
            raise BrokenPipeError(str(e))

    def _recv_exact(self, n: int) -> memoryview:
        buf = bytearray(n)
        view = memoryview(buf)
        got = 0
        while got < n:
            k = self._sock.recv_into(view[got:], n - got)
            if k == 0:
                raise EOFError("socket closed by peer")
            got += k
        return memoryview(buf)

    def recv_bytes(self):
        (n,) = _FRAME.unpack(self._recv_exact(_FRAME.size))
        return self._recv_exact(n)

    def poll(self, timeout: float = 0.0) -> bool:
        import select
        r, _, _ = select.select([self._sock], [], [], max(0.0, timeout))
        return bool(r)

    def close(self):
        try:
            self._sock.shutdown(socketlib.SHUT_RDWR)
        except OSError as e:
            # ENOTCONN when the peer closed first: normal; still logged
            # so half-closed-socket issues leave a trail
            _log.debug("socket shutdown during close: %r", e)
        self._sock.close()


def _serve_socket_actor(conn: _SockConn, *, apply_device_env: bool = False):
    """One accepted connection == one actor: read the spawn request,
    then run the standard server loop until shutdown/EOF."""
    try:
        req = wire.deserialize(conn.recv_bytes())
    except (EOFError, OSError):
        conn.close()
        return
    # tracing controllers append a boot-extras dict (a --listen host has
    # no inherited REPRO_TRACE env, so the flag must ride the request)
    tag, factory, args, kwargs, spec, *rest = req
    assert tag == "spawn", f"bad socket hello {tag!r}"
    boot = {"device_spec": spec, "apply_device_env": apply_device_env}
    if rest:
        boot.update(rest[0])
    try:
        _actor_server(conn, factory, args, kwargs, boot)
    finally:
        conn.close()


def serve_actor_host(host: str = "0.0.0.0", port: int = 0, *,
                     once: bool = False, ready=None):
    """Actor host: accept connections, serve one actor per connection
    (each on its own thread) until killed.  This is what
    ``repro.launch.train --listen HOST:PORT`` runs on a remote machine;
    the host's own device set (``XLA_FLAGS`` at launch) is the device
    world every actor it hosts shares -- run one host per submesh."""
    ls = socketlib.socket()
    ls.setsockopt(socketlib.SOL_SOCKET, socketlib.SO_REUSEADDR, 1)
    ls.bind((host, port))
    ls.listen(16)
    if ready is not None:
        ready(ls.getsockname()[1])
    try:
        while True:
            sock, peer = ls.accept()
            t = threading.Thread(
                target=_serve_socket_actor, args=(_SockConn(sock),),
                daemon=True, name=f"actor-host-{peer}")
            t.start()
            if once:
                t.join()
                return
    finally:
        ls.close()


def _socket_host_once(report_conn, device_spec):
    """Self-host helper child: bind an ephemeral port, report it, serve
    exactly one actor.  Runs in a fresh spawned interpreter, so the
    device spec's XLA flags still apply."""
    if device_spec is not None:
        device_spec.apply_env()
    ls = socketlib.socket()
    ls.bind(("127.0.0.1", 0))
    ls.listen(1)
    report_conn.send(ls.getsockname()[1])
    report_conn.close()
    sock, _ = ls.accept()
    ls.close()
    _serve_socket_actor(_SockConn(sock), apply_device_env=False)


class SocketTransport(_RpcTransport):
    """The wire format over TCP: executors on independently launched
    hosts (``--listen``), or -- with no address -- a self-hosted local
    helper process serving one actor on an ephemeral localhost port (the
    testing/CI mode; also what lets ``REPRO_TRANSPORT=socket`` rerun a
    whole suite over sockets with zero wiring).  A dropped connection or
    killed host surfaces as ``ActorDied`` instead of a hang."""

    def __init__(self, factory, args=(), kwargs=None, *,
                 address: Optional[Tuple[str, int]] = None,
                 spawn_timeout: float = 180.0, call_timeout: float = 600.0,
                 device_spec: Optional[DeviceSpec] = None):
        self._proc = None
        self.address = address
        if address is None:
            ctx = mp.get_context("spawn")
            pconn, cconn = ctx.Pipe()
            self._proc = ctx.Process(
                target=_socket_host_once, args=(cconn, device_spec),
                daemon=True,
                name=f"sockhost-{getattr(factory, '__name__', '?')}")
            self._proc.start()
            cconn.close()
            if not pconn.poll(spawn_timeout):
                self._proc.kill()
                raise TimeoutError("socket self-host never reported a port")
            self.address = ("127.0.0.1", pconn.recv())
            pconn.close()
        sock = socketlib.create_connection(self.address,
                                           timeout=spawn_timeout)
        self._init_rpc(_SockConn(sock), _PlainCodec(), call_timeout)
        req = ("spawn", factory, tuple(args), kwargs or {}, device_spec)
        if obs_trace.enabled():
            req = req + ({"trace": True},)
        self._conn.send_bytes(wire.serialize(req))
        status, payload = self._recv(spawn_timeout, what="actor handshake")
        if status == "hello_err":
            self._teardown()
            raise _unpack_exc(payload, getattr(factory, "__name__", "?"))
        assert status == "hello", f"bad handshake: {status!r}"
        self._desc = payload
        self._clock_sync()

    def _peer_alive(self) -> bool:
        # the socket itself is the liveness signal: a dead peer turns
        # into EOF/ECONNRESET on the next poll/recv.  For a self-hosted
        # helper we can do better and watch the process.
        if self._proc is not None:
            return self._proc.is_alive()
        return True

    def _exit_desc(self) -> str:
        if self._proc is not None:
            return (f"self-hosted process (pid {self._proc.pid}) exited "
                    f"with code {self._proc.exitcode}")
        return f"connection to {self.address} dropped"

    def join(self, timeout: Optional[float] = None):
        if self._proc is not None:
            self._proc.join(timeout)

    def _teardown(self):
        if self._proc is not None:
            if self._proc.is_alive():
                self._proc.join(timeout=5.0)
            if self._proc.is_alive():
                self._proc.terminate()
                self._proc.join(timeout=5.0)
            if self._proc.is_alive():        # pragma: no cover
                self._proc.kill()
                self._proc.join(timeout=5.0)
        self._codec.close()
        self._conn.close()


def close_all_actors():
    """Close every live remote-backed actor (test/teardown hygiene) and
    unlink any shm segment a crashed transport left registered."""
    for t in list(_LIVE_TRANSPORTS):
        t.close()
    with _SHM_REGISTRY_LOCK:
        leaked = list(_SHM_REGISTRY.values())
    for seg in leaked:                       # pragma: no cover - belt+braces
        _shm_unlink(seg)


# ------------------------------------------------------------------ handles --

class ActorHandle:
    """What the controller holds: typed endpoints over a Transport.

    Identity is the handle object itself -- ``as_handle`` returns one
    canonical handle per in-process executor, so channel/controller
    membership checks (``ch.inbound in self.generators``) keep working.
    """

    def __init__(self, transport: Transport):
        self.transport = transport
        d = transport.describe()
        self.name: str = d["name"]
        self.role: str = d["role"]
        self.chunk_hooks: bool = d.get("chunk_hooks", False)
        self.engine_hooks: bool = d.get("engine_hooks", False)
        self.staged_weights: bool = d.get("staged_weights", False)
        self._pinned_hooks: bool = d.get("pinned_hooks", False)

    @property
    def mesh(self):
        return self.transport.mesh

    # -- typed endpoints ----------------------------------------------------

    def call(self, method: str, *args, timeout: Optional[float] = None,
             **kwargs):
        """Synchronous RPC: invoke a method (or read an attribute) on the
        actor and return the result; remote exceptions re-raise here."""
        return self.transport.call(method, args, kwargs, timeout)

    def cast(self, method: str, *args, **kwargs):
        """Fire-and-forget send, FIFO-ordered with later calls through
        this handle; errors surface on the next ``call``."""
        self.transport.cast(method, args, kwargs)

    def healthy(self) -> bool:
        return self.transport.healthy()

    def drain_trace(self) -> int:
        """Explicitly pull this actor's buffered trace events (remote
        transports only; the piggyback path usually makes this moot)."""
        return self.transport.drain_trace()

    def join(self, timeout: Optional[float] = None):
        self.transport.join(timeout)

    def close(self):
        self.transport.close()

    def respawn(self) -> "ActorHandle":
        """Rebuild this actor from its recorded spawn spec, swapping the
        fresh transport in place.

        Identity is the handle object (see class docstring), so every
        structure holding it -- pools, weight channels, controller maps
        -- follows the respawn automatically.  The old transport is
        closed first, which reaps the dead process and unlinks any shm
        segments it owned; the new executor starts blank (``init`` and
        weight replay are the supervisor's job)."""
        spec = getattr(self, "spawn_spec", None)
        if spec is None:
            raise RuntimeError(
                f"actor '{self.name}' has no recorded spawn spec "
                "(not created via spawn_actor?)")
        try:
            self.transport.close()
        except Exception as e:               # pragma: no cover - diagnostics
            _log.debug("closing dead transport for '%s': %r", self.name, e)
        t = spec.build()
        self.transport = t
        d = t.describe()
        self.name = d["name"]
        self.role = d["role"]
        self.chunk_hooks = d.get("chunk_hooks", False)
        self.engine_hooks = d.get("engine_hooks", False)
        self.staged_weights = d.get("staged_weights", False)
        self._pinned_hooks = d.get("pinned_hooks", False)
        return self

    # -- chunk-stepping collaborator surface (RolloutScheduler) -------------
    # The scheduler's executor contract is advance_chunk(job, state) with
    # in-place job mutation.  Over a process boundary the mutation happens
    # on the child's copy, so the handle routes through advance_chunk_rt
    # (which returns the job) and mirrors the mutated fields back onto the
    # caller's job object -- inproc this is the identity.  For remote
    # actors the admission-time params snapshot is *pinned* actor-side
    # (``begin_batch_pinned``): the job carries a small reference instead
    # of round-tripping the whole weight pytree on every chunk.

    def begin_batch(self, batch_index=None):
        if self.transport.remote and self._pinned_hooks:
            return self.call("begin_batch_pinned", batch_index)
        return self.call("begin_batch", batch_index)

    def advance_chunk(self, job, state):
        job2, state = self.call("advance_chunk_rt", job, state)
        if job2 is not job:
            job.__dict__.update(job2.__dict__)
        return state

    def emit_batch(self, job, state):
        return self.call("emit_batch", job, state)

    def __repr__(self):
        kind = type(self.transport).__name__
        return f"<ActorHandle {self.name!r} role={self.role} via {kind}>"


def as_handle(x) -> ActorHandle:
    """Canonical handle for ``x``: handles pass through; a raw executor
    gets one cached ``InprocTransport`` handle (identity-stable, so every
    wiring site that names the same executor shares the same handle)."""
    if isinstance(x, ActorHandle):
        return x
    h = getattr(x, "_actor_handle", None)
    if h is None:
        h = ActorHandle(InprocTransport(x))
        try:
            x._actor_handle = h
        except (AttributeError, TypeError):  # pragma: no cover - slots etc.
            pass
    return h


_SOCKET_ADDR_COUNTER = [0]


def _next_socket_address() -> Optional[Tuple[str, int]]:
    """Round-robin over ``REPRO_SOCKET_ADDRS`` ("host:port,host:port");
    None (self-host) when unset."""
    addrs = os.environ.get("REPRO_SOCKET_ADDRS", "").strip()
    if not addrs:
        return None
    parts = [a.strip() for a in addrs.split(",") if a.strip()]
    host, _, port = parts[_SOCKET_ADDR_COUNTER[0] % len(parts)] \
        .rpartition(":")
    _SOCKET_ADDR_COUNTER[0] += 1
    return (host or "127.0.0.1", int(port))


def _refuse_local_child_on_tpu(transport: str):
    """A TPU chip belongs to one process: once this process has brought
    up a TPU backend, a local child that needs the chip would fail or sit
    out the whole spawn handshake.  Say so at once instead."""
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized() and \
            jax.default_backend() == "tpu":
        raise RuntimeError(
            f"transport {transport!r} would start a local child actor, but "
            "this process already holds the TPU (a chip belongs to one "
            "process).  Use transport='inproc' with submeshes on one host, "
            "or start the controller before anything touches JAX.")


@dataclass(frozen=True)
class SpawnSpec:
    """Everything needed to (re)build an actor identically: recorded on
    every handle by ``spawn_actor`` (``handle.spawn_spec``), so a
    supervisor can respawn a dead actor -- same factory, same seed and
    kwargs, same transport, device placement and address -- or a pool
    can hot-attach a spare built from a spec alone."""

    factory: Any
    args: Tuple = ()
    kwargs: Any = None
    transport: str = "inproc"
    spawn_timeout: float = 180.0
    call_timeout: float = 600.0
    device_spec: Optional[DeviceSpec] = None
    address: Optional[Tuple[str, int]] = None

    def build(self) -> Transport:
        """A fresh transport hosting a newly constructed executor."""
        kwargs = dict(self.kwargs or {})
        if self.transport == "inproc":
            if self.device_spec is not None and \
                    self.device_spec.mesh_shape and "mesh" not in kwargs:
                kwargs["mesh"] = self.device_spec.build_mesh()
            return InprocTransport(self.factory(*self.args, **kwargs))
        if self.address is None or self.transport != "socket":
            _refuse_local_child_on_tpu(self.transport)
        if self.transport == "proc":
            return ProcTransport(
                self.factory, self.args, kwargs,
                spawn_timeout=self.spawn_timeout,
                call_timeout=self.call_timeout,
                device_spec=self.device_spec)
        if self.transport == "shm":
            return ShmTransport(
                self.factory, self.args, kwargs,
                spawn_timeout=self.spawn_timeout,
                call_timeout=self.call_timeout,
                device_spec=self.device_spec)
        if self.transport == "socket":
            return SocketTransport(
                self.factory, self.args, kwargs, address=self.address,
                spawn_timeout=self.spawn_timeout,
                call_timeout=self.call_timeout,
                device_spec=self.device_spec)
        raise ValueError(
            f"unknown transport {self.transport!r}: expected 'inproc', "
            f"'proc', 'shm' or 'socket'")

    def spawn(self) -> ActorHandle:
        """Build the transport and wrap it in a handle carrying this
        spec (the respawnable form of ``spawn_actor``)."""
        h = ActorHandle(self.build())
        h.spawn_spec = self
        return h


def spawn_actor(factory, *args, transport: Optional[str] = None,
                spawn_timeout: float = 180.0, call_timeout: float = 600.0,
                device_spec: Optional[DeviceSpec] = None,
                address: Optional[Tuple[str, int]] = None,
                **kwargs) -> ActorHandle:
    """Construct an executor behind an ``ActorHandle``.

    ``transport`` is ``"inproc"`` (construct here, direct calls),
    ``"proc"`` (spawned subprocess, pipe wire payloads), ``"shm"``
    (spawned subprocess, large payloads over shared-memory rings) or
    ``"socket"`` (TCP to ``address``, a ``--listen`` host, or a local
    self-hosted helper when ``address`` is None /
    ``REPRO_SOCKET_ADDRS`` is unset); ``None`` reads
    ``REPRO_TRANSPORT`` (default ``inproc``).  ``device_spec`` pins the
    child's device count / submesh.  The factory and arguments must be
    picklable for every remote transport.

    The resolved spec is recorded as ``handle.spawn_spec``, which is
    what lets a ``Supervisor`` respawn the actor after a crash.
    """
    transport = transport or os.environ.get("REPRO_TRANSPORT", "inproc")
    if transport == "socket" and address is None:
        address = _next_socket_address()
    spec = SpawnSpec(factory, tuple(args), dict(kwargs), transport,
                     spawn_timeout, call_timeout, device_spec, address)
    if transport == "inproc":
        # keep the identity-caching as_handle path: wiring sites that
        # name the same raw executor must share one canonical handle
        if device_spec is not None and device_spec.mesh_shape and \
                "mesh" not in kwargs:
            kwargs["mesh"] = device_spec.build_mesh()
        h = as_handle(factory(*args, **kwargs))
        h.spawn_spec = spec
        return h
    if transport in ("proc", "shm", "socket"):
        return spec.spawn()
    raise ValueError(
        f"unknown transport {transport!r}: expected 'inproc', 'proc', "
        f"'shm' or 'socket'")
