"""Socket pattern wrappers with reference-equivalent semantics.

Patterns and options mirror the reference's "backend API" (SURVEY.md §5):

- PUSH(bind, SNDHWM, IMMEDIATE) -> PULL(connect-to-all, RCVHWM) for the data
  stream: backpressure via small HWMs, fair fan-in, at-most-once delivery,
  no ordering guarantee (``publisher.py:22-27`` <-> ``dataset.py:73-78``).
- PAIR(bind) <-> PAIR(connect) duplex control with HWM 10, linger and
  send/recv timeouts (``btb/duplex.py:12-18`` <-> ``btt/duplex.py:12-18``),
  message ids ``btmid`` + instance ids ``btid`` stamped on send
  (``btt/duplex.py:44-67``).
- REQ(RELAXED, CORRELATE) <-> REP for environment RPC
  (``btt/env.py:36-42`` <-> ``btb/env.py:212-216``).

Failure semantics are fail-fast: a poll timeout raises
``ReceiveTimeoutError`` (the reference asserts/raises on ``zmq.error.Again``,
``dataset.py:98-99``, ``btt/env.py:116-124``).
"""

from __future__ import annotations

# bjx: hot-path (recv/decode sits on the ingest critical path: BJX102
# flags any blocking device sync added to this module)

import os
import threading
import time

import zmq

from blendjax import constants
from blendjax.transport.shm import (
    REGISTRY_ENV,
    ShmCapacityError,
    ShmRing,
    resolve_message,
)
from blendjax.transport.wire import (
    DEFAULT_COMPRESS_MIN_BYTES,
    WireCompressState,
    decode_message,
    encode_message,
)


class ReceiveTimeoutError(TimeoutError):
    """No message arrived within the timeout — treat the peer as failed/hung."""


_context_lock = threading.Lock()
_context = None
_context_pid = None


def zmq_context() -> zmq.Context:
    """Process-wide ZMQ context (re-created after fork for DataLoader-style
    worker processes, matching the reference's lazy per-worker socket
    construction in ``dataset.py:64-78``)."""
    global _context, _context_pid
    with _context_lock:
        if _context is None or _context_pid != os.getpid():
            _context = zmq.Context()
            _context_pid = os.getpid()
        return _context


def term_context() -> None:
    """Terminate the process-wide context, BLOCKING until every closed
    socket's pending messages are flushed or its LINGER expires.

    This is the only operation that actually guarantees delivery of a
    finite stream's tail: ``socket.close()`` returns immediately and
    leaves flushing to the IO thread, which dies with the interpreter —
    a producer that publishes its last message and exits loses it
    sporadically unless something waits, and pyzmq deliberately skips
    context termination during interpreter shutdown. Call it at the END
    of a producer process, after closing all sockets (a fresh context
    is created transparently if sockets are opened afterwards).
    """
    global _context
    with _context_lock:
        ctx = _context
        _context = None
    if ctx is not None and _context_pid == os.getpid():
        # (a context inherited across fork is never terminated here —
        # its IO thread did not survive the fork)
        ctx.term()


def _as_frames(raw) -> list:
    return raw if isinstance(raw, list) else [raw]



class _Channel:
    """Shared socket plumbing: context-managed close + poll/recv/decode."""

    sock: zmq.Socket
    allow_pickle: bool = True
    # Only the bulk data stream accounts its frames into the
    # wire.raw_bytes/wire.compressed_bytes pair (DataReceiverSocket
    # flips this True): control/RPC arrays through the same codec would
    # pollute the published compression ratio.
    wire_metrics: bool = False

    def _register_poller(self) -> None:
        self.poller = zmq.Poller()
        self.poller.register(self.sock, zmq.POLLIN)

    # Deferred run-length decode: class-level default so every channel
    # decodes identically unless its owner (DataReceiverSocket) opts in.
    defer_rle: bool = False

    def _poll_frames(self, timeoutms: int):
        """Receive one raw multipart message within ``timeoutms``;
        returns the frame buffers or ``None`` on timeout. Decode is
        separate (:meth:`decode_frames`) so callers owning an inflate
        pool can pipeline receive against decode."""
        socks = dict(self.poller.poll(timeoutms))
        if self.sock not in socks:
            return None
        frames = _as_frames(self.sock.recv_multipart(copy=False))
        return [f.buffer for f in frames]

    def decode_frames(self, buffers, copy_arrays: bool = False):
        """Decode raw frame buffers with this channel's configured
        semantics (pickle policy, wire metrics, deferred rle) — the ONE
        decode call both the inline and the decode-ahead receive paths
        share. Intra-message parallel inflate stays a direct
        ``decode_message(inflate_pool=)`` surface: the stream path's
        whole-message decode-ahead subsumes it and must not re-enter
        the same executor from inside a decode job."""
        msg = decode_message(
            buffers, copy_arrays=copy_arrays,
            allow_pickle=self.allow_pickle,
            count_metrics=self.wire_metrics,
            defer_rle=self.defer_rle,
        )
        if isinstance(msg, dict) and "_shm" in msg:
            # Co-located producer: the wire carried only a descriptor;
            # the tensor bytes come straight out of the shared-memory
            # ring (blendjax.transport.shm). A torn generation leaves a
            # `_shm_torn` marker for the stream layer to account + skip.
            msg = resolve_message(msg)
        return msg

    def _poll_recv(self, timeoutms: int, copy_arrays: bool):
        """Receive+decode one message within ``timeoutms``; returns
        ``(message, raw_buffers)`` or ``None`` on timeout."""
        buffers = self._poll_frames(timeoutms)
        if buffers is None:
            return None
        return self.decode_frames(buffers, copy_arrays), buffers

    def close(self):
        # No linger override: close() keeps queued messages alive for
        # the IO thread to flush, bounded by the socket's configured
        # LINGER (``close(0)`` here silently DISCARDED them). Note the
        # flush is only GUARANTEED if the process lives long enough —
        # finite-stream producers must call
        # :func:`blendjax.transport.term_context` before exiting, which
        # blocks until the flush completes or LINGER expires.
        self.sock.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class _SentTracker:
    """`zmq.MessageTracker` stand-in for shm publishes: the payload was
    copied into the ring before send, so it is 'done' immediately."""

    done = True

    def wait(self, timeout=None):
        return None


_DONE_TRACKER = _SentTracker()


class DataPublisherSocket(_Channel):
    """Producer end of the data stream: PUSH, bind side.

    Reference: ``pkg_blender/blendtorch/btb/publisher.py:4-43``. The small
    send HWM blocks the renderer when consumers fall behind, which is the
    framework's backpressure mechanism (``examples/datagen/Readme.md:168-175``).

    Zero-copy hazard: with ``copy=False`` (the default) ndarray payloads are
    handed to the socket by reference and transmitted asynchronously after
    ``publish`` returns. A producer that mutates or reuses its buffer (e.g.
    an offscreen render target) must pass ``copy=True`` — the reference
    always copied implicitly by pickling at send time (``publisher.py:43``).
    """

    def __init__(
        self,
        bind_addr: str,
        btid: int | None = None,
        send_hwm: int = constants.DEFAULT_SEND_HWM,
        codec: str = "tensor",
        lingerms: int = 0,
        copy: bool = False,
        compress_level: int = 0,
        compress_min_bytes: int = DEFAULT_COMPRESS_MIN_BYTES,
        compress_rle: bool = False,
        rle_cap: int | None = None,
        quantize_f16=(),
        lineage: bool = True,
        telemetry_every: int = 64,
        trace_every: int = 64,
        shm=None,
        shm_timeout_s: float = 5.0,
    ):
        self.codec = codec
        self.btid = btid
        self.copy = copy
        # Zero-copy local transport (docs/wire-protocol.md "Shared-memory
        # descriptors"): with ``shm`` set, ndarray payloads are written
        # into a shared-memory ring and only a tiny descriptor rides the
        # socket — same-host consumers attach and read the slot with no
        # pickle/inflate. Pass an ``ShmRing`` to share one, ``True``/an
        # int slot count to lazily create a ring sized from the first
        # payload. Messages without arrays (or that outgrow the slot)
        # fall back to the wire codecs transparently, so remote-capable
        # code needs no changes.
        self._shm_timeout_s = float(shm_timeout_s)
        self._shm_owned = False
        if isinstance(shm, ShmRing):
            self._shm_ring = shm
            self._shm_slots = shm.slots
        elif shm:
            self._shm_ring = None
            self._shm_slots = 4 if shm is True else int(shm)
            self._shm_owned = True
        else:
            self._shm_ring = None
            self._shm_slots = 0
        # Per-publisher wire compression (tensor codec only): level > 0
        # ships large array frames as zlib "ndz" entries. Trades producer
        # CPU for wire bytes — the right trade across hosts, the wrong
        # one on ipc/loopback (docs/performance.md).
        self.compress_level = int(compress_level)
        self.compress_min_bytes = int(compress_min_bytes)
        # Run-length "ndr" wire frames (docs/wire-protocol.md): cheap
        # host encode, near-free consumer inflate, and — on the fused
        # tile path — expansion deferred INTO the consumer's train jit.
        # rle_cap pins the packed per-row capacity fleet-wide (the
        # TileBatchPublisher capacity contract); quantize_f16 names
        # float sidecar fields to ship half-width (lossy; exact for
        # integer pixel coordinates up to 2048).
        self.compress_rle = bool(compress_rle)
        self.rle_cap = int(rle_cap) if rle_cap else None
        self.quantize_f16 = tuple(quantize_f16)
        # Reusable per-publisher compression state: compressobj
        # templates, the incompressible-key skip memo, sticky rle caps.
        self._wire_state = (
            WireCompressState()
            if (self.compress_level > 0 or self.compress_rle) else None
        )
        # Frame lineage (docs/observability.md): every message carries a
        # wall + monotonic publish time and a per-publisher monotonic
        # sequence number, and every `telemetry_every`-th message
        # piggybacks a snapshot of this process's metrics registry —
        # the consumer side (blendjax.obs.lineage) turns these into
        # per-producer staleness histograms, exact drop/reorder counts,
        # and a fleet telemetry view, all without a second socket.
        # lineage=False restores the pre-telemetry wire shape.
        self.lineage = bool(lineage)
        self.telemetry_every = int(telemetry_every) if lineage else 0
        # Distributed frame tracing (blendjax.obs.trace): every
        # trace_every-th message additionally carries a `_trace` context
        # — trace id, producer btid/pid, and a growing list of
        # [stage, t_mono, t_wall] stamps each downstream stage appends
        # in place. Off the sampled path the cost is one modulo check;
        # trace_every=0 disables stamping entirely (and lineage=False
        # implies it, like telemetry).
        self.trace_every = int(trace_every) if lineage else 0
        self._pid = os.getpid()
        self._seq = 0
        self._created_wall = time.time()
        self._tel_mark = (0, self._created_wall)  # (seq, wall) at last snapshot
        self.sock = zmq_context().socket(zmq.PUSH)
        self.sock.setsockopt(zmq.SNDHWM, send_hwm)
        self.sock.setsockopt(zmq.IMMEDIATE, 1)
        self.sock.setsockopt(zmq.LINGER, lingerms)
        self.sock.bind(bind_addr)
        # Wildcard ports ("tcp://host:*") resolve at bind time; expose the
        # effective address so launchers/tests can hand it to consumers.
        self.addr = self.sock.getsockopt_string(zmq.LAST_ENDPOINT)

    def publish(self, **kwargs):
        """Publish a message dict; stamps ``btid`` for provenance
        (reference stamps every payload, ``publisher.py:42``) plus the
        lineage stamps (seq + publish times; see ``__init__``)."""
        data = self._stamp({"btid": self.btid, **kwargs})
        if self._shm_slots:
            frames = self._encode_shm(data)
            if frames is not None:
                # descriptor frames are tiny: copy-send, nothing to track
                self.sock.send_multipart(frames, copy=True)
                return
        self.sock.send_multipart(
            self._encode(data), copy=self.copy
        )

    def _encode_shm(self, data: dict) -> list | None:
        """Write the message's arrays into the shm ring and encode the
        descriptor message; ``None`` means "use the wire codecs" (no
        array payload, or the payload outgrew the slot)."""
        import numpy as np

        arrs = {
            k: v for k, v in data.items()
            if isinstance(v, np.ndarray) and v.ndim >= 1
        }
        if not arrs:
            return None
        ring = self._shm_ring
        if ring is None:
            # size the ring from the first payload (stable shapes are the
            # co-located steady state), with headroom for stamp jitter
            slot_bytes = sum(v.nbytes + 64 for v in arrs.values()) * 2
            ring = ShmRing(
                slots=self._shm_slots, slot_bytes=slot_bytes,
                btid=self.btid,
            )
            self._shm_ring = ring
        try:
            desc = ring.write(arrs, timeout_s=self._shm_timeout_s)
        except ShmCapacityError:
            from blendjax.utils.metrics import metrics

            metrics.count("wire.shm_fallbacks")
            return None
        small = {k: v for k, v in data.items() if k not in arrs}
        small["_shm"] = desc
        return self._encode(small)

    def _stamp(self, data: dict) -> dict:
        if not self.lineage:
            return data
        data["_seq"] = self._seq
        data["_pub_wall"] = time.time()
        data["_pub_mono"] = time.monotonic()
        if self.telemetry_every and self._seq % self.telemetry_every == 0:
            data["_telemetry"] = self._telemetry_snapshot()
        if self.trace_every and self._seq % self.trace_every == 0:
            # Sampled end-to-end frame trace (blendjax.obs.trace): the
            # shape is inlined (not imported) so producer processes —
            # Blender's Python — need nothing beyond this module. The
            # trace id is globally unique per (producer pid, seq).
            data["_trace"] = {
                "id": f"{self.btid}-{self._pid}-{self._seq}",
                "btid": self.btid,
                "pid": self._pid,
                "stages": [["publish", time.monotonic(), time.time()]],
            }
        self._seq += 1
        return data

    def _telemetry_snapshot(self) -> dict:
        """Compact, msgpack-native snapshot of this process's metrics
        (producer render spans, publish rate, frame counter) — the
        piggyback payload the consumer's fleet view aggregates."""
        from blendjax.utils.metrics import metrics

        now = time.time()
        last_seq, last_wall = self._tel_mark
        dt = max(now - last_wall, 1e-9)
        self._tel_mark = (self._seq, now)
        report = metrics.report()
        return {
            "seq": int(self._seq),
            "uptime_s": round(now - self._created_wall, 3),
            # messages/s since the previous snapshot (0.0 on the first)
            "mps": round((self._seq - last_seq) / dt, 3),
            "counters": {k: int(v) for k, v in report["counters"].items()},
            "spans": {
                k: {
                    "count": int(v["count"]),
                    "mean_ms": round(float(v["mean_ms"]), 3),
                    "p95_ms": round(float(v.get("p95_ms", 0.0)), 3),
                }
                for k, v in report["spans"].items()
            },
        }

    def _encode(self, data: dict) -> list:
        return encode_message(
            data, codec=self.codec,
            compress_level=self.compress_level,
            compress_min_bytes=self.compress_min_bytes,
            compress_rle=self.compress_rle,
            rle_cap=self.rle_cap,
            quantize_f16=self.quantize_f16,
            state=self._wire_state,
        )

    def publish_tracked(self, **kwargs):
        """Zero-copy publish returning a ``zmq.MessageTracker``.

        ``tracker.done`` flips True once the IO thread no longer references
        the payload buffers, so a producer rotating a fixed buffer pool can
        ``tracker.wait()`` before rendering into a slot again. Unlike
        HWM-based pool sizing this bounds buffer reuse for *any* number of
        connected consumers: PUSH keeps one queue per pipe, so per-pipe HWM
        alone does not cap the total number of in-flight messages."""
        data = self._stamp({"btid": self.btid, **kwargs})
        if self._shm_slots:
            frames = self._encode_shm(data)
            if frames is not None:
                # the ring copied the arrays already: the caller's buffers
                # are free the moment we return, so the tracker is a
                # pre-completed stand-in (the ring's ack counters — not
                # MessageTracker — now bound slot reuse)
                self.sock.send_multipart(frames, copy=True)
                return _DONE_TRACKER
        return self.sock.send_multipart(
            self._encode(data), copy=False, track=True
        )

    def close(self):
        super().close()
        ring = self._shm_ring
        if ring is not None and self._shm_owned:
            ring.close()
            # Under a fleet launcher the registry owns the unlink (after
            # the consumer drains); standalone producers unlink on clean
            # close so nothing leaks in /dev/shm. ShmRing.unlink() is
            # idempotent, so racing the launcher is harmless.
            if not os.environ.get(REGISTRY_ENV):
                ring.unlink()



class DataReceiverSocket(_Channel):
    """Consumer end: PULL, connects to *all* producer addresses.

    Reference: ``pkg_pytorch/blendtorch/btt/dataset.py:68-111``. Fair-queued
    fan-in across producers; at-most-once per consumer; raises on timeout.
    ``recv`` returns ``(message, raw_frames)`` so a recorder can tee the
    exact wire bytes without re-encoding (reference tees raw pickles in the
    hot loop, ``dataset.py:100-103``).
    """

    wire_metrics = True  # the data stream IS the wire.* counter pair

    def __init__(
        self,
        addresses,
        queue_size: int = constants.DEFAULT_QUEUE_SIZE,
        timeoutms: int = constants.DEFAULT_TIMEOUTMS,
        allow_pickle: bool = True,
        defer_rle: bool = False,
    ):
        if isinstance(addresses, str):
            addresses = [addresses]
        self.addresses = list(addresses)
        self.timeoutms = timeoutms
        self.allow_pickle = allow_pickle
        # defer_rle: leave "ndr" frames of prebatched messages packed
        # for a device-side expansion plan (the fused tile path) —
        # see blendjax.transport.wire.TensorCodec.decode.
        self.defer_rle = bool(defer_rle)
        self.sock = zmq_context().socket(zmq.PULL)
        self.sock.setsockopt(zmq.RCVHWM, queue_size)
        self.sock.setsockopt(zmq.LINGER, 0)
        for addr in self.addresses:
            self.sock.connect(addr)
        self._register_poller()

    def recv(self, timeoutms: int | None = None, copy_arrays: bool = False):
        t = self.timeoutms if timeoutms is None else timeoutms
        out = self._poll_recv(t, copy_arrays)
        if out is None:
            raise ReceiveTimeoutError(
                f"no message within {t} ms from {self.addresses}"
            )
        return out

    def recv_frames(self, timeoutms: int | None = None):
        """Receive one message's RAW frame buffers (no decode) — the
        receive half of the decode-ahead pipeline (RemoteStream hands
        the buffers to a shared inflate executor and yields decoded
        messages in receive order)."""
        t = self.timeoutms if timeoutms is None else timeoutms
        buffers = self._poll_frames(t)
        if buffers is None:
            raise ReceiveTimeoutError(
                f"no message within {t} ms from {self.addresses}"
            )
        return buffers

    # -- elastic membership (fleet controller substrate) ---------------------
    # ZMQ sockets are single-thread: both calls below must run on the
    # thread that owns this socket (RemoteStream queues membership ops
    # and applies them from its iterating thread — see
    # ``blendjax.data.stream``).

    def connect(self, addr: str) -> None:
        """Admit one more producer endpoint into the fan-in (idempotent
        at the bookkeeping level; duplicate connects are skipped)."""
        if addr in self.addresses:
            return
        self.sock.connect(addr)
        self.addresses.append(addr)

    def disconnect(self, addr: str) -> None:
        """Retire one producer endpoint. NOTE: zmq drops messages still
        queued on that endpoint's pipe — drain first (retire the
        producer, keep receiving through a grace window) or the tail is
        lost."""
        try:
            self.sock.disconnect(addr)
        except zmq.ZMQError:
            pass  # already gone (e.g. peer closed the transport)
        if addr in self.addresses:
            self.addresses.remove(addr)



class PairChannel(_Channel):
    """Duplex control channel (PAIR<->PAIR), producer binds / consumer connects.

    Reference: ``btt/duplex.py:8-67`` and ``btb/duplex.py:8-66``. ``send``
    stamps ``btid`` plus a fresh random message id ``btmid``; ``recv``
    returns ``None`` on timeout (densityopt polls with ``timeoutms=0`` each
    frame, ``supershape.blend.py:26-37``).
    """

    def __init__(
        self,
        addr: str,
        btid: int | None = None,
        bind: bool = False,
        hwm: int = constants.DEFAULT_SEND_HWM,
        lingerms: int = 0,
        codec: str = "tensor",
        default_timeoutms: int = constants.DEFAULT_TIMEOUTMS,
        allow_pickle: bool = True,
    ):
        self.btid = btid
        self.codec = codec
        self.default_timeoutms = default_timeoutms
        self.allow_pickle = allow_pickle
        self.sock = zmq_context().socket(zmq.PAIR)
        self.sock.setsockopt(zmq.SNDHWM, hwm)
        self.sock.setsockopt(zmq.RCVHWM, hwm)
        self.sock.setsockopt(zmq.LINGER, lingerms)
        if bind:
            self.sock.bind(addr)
            self.addr = self.sock.getsockopt_string(zmq.LAST_ENDPOINT)
        else:
            self.sock.connect(addr)
            self.addr = addr
        self._register_poller()

    def send(self, **kwargs) -> bytes:
        """Send a message; returns the generated ``btmid`` message id.

        Control messages are small, so payloads are copied at send time
        (no buffer-reuse hazard, unlike the bulk data stream).
        """
        btmid = os.urandom(4)
        data = {"btid": self.btid, "btmid": btmid, **kwargs}
        self.sock.send_multipart(encode_message(data, codec=self.codec), copy=True)
        return btmid

    def recv(self, timeoutms: int | None = None):
        """Receive one message or ``None`` if nothing arrives in time."""
        t = self.default_timeoutms if timeoutms is None else timeoutms
        out = self._poll_recv(t, copy_arrays=True)
        return None if out is None else out[0]



class RpcClient(_Channel):
    """Blocking request/reply client (REQ with RELAXED+CORRELATE).

    Reference: ``btt/env.py:36-42,111-124``. RELAXED+CORRELATE let the REQ
    socket recover from a lost reply instead of wedging, and timeouts raise
    so a dead environment fails fast.
    """

    def __init__(self, addr: str, timeoutms: int = constants.DEFAULT_TIMEOUTMS,
                 codec: str = "tensor", allow_pickle: bool = True):
        self.codec = codec
        self.timeoutms = timeoutms
        self.addr = addr
        self.allow_pickle = allow_pickle
        self.sock = zmq_context().socket(zmq.REQ)
        self.sock.setsockopt(zmq.REQ_RELAXED, 1)
        self.sock.setsockopt(zmq.REQ_CORRELATE, 1)
        self.sock.setsockopt(zmq.SNDTIMEO, timeoutms)
        self.sock.setsockopt(zmq.RCVTIMEO, timeoutms)
        self.sock.setsockopt(zmq.LINGER, 0)
        self.sock.connect(addr)

    def call(self, **kwargs) -> dict:
        try:
            self.sock.send_multipart(
                encode_message(kwargs, codec=self.codec), copy=True
            )
            frames = _as_frames(self.sock.recv_multipart(copy=False))
        except zmq.error.Again as e:
            raise ReceiveTimeoutError(f"rpc to {self.addr} timed out") from e
        return decode_message(
            [f.buffer for f in frames],
            copy_arrays=True,
            allow_pickle=self.allow_pickle,
        )



class RpcServer(_Channel):
    """Reply side of the RPC pattern (REP, bind).

    Reference: ``btb/env.py:212-216``. ``recv``/``reply`` are split so the
    producer's STATE_REQ/STATE_REP machine (``btb/env.py:206-252``) can
    interleave them with frame callbacks; ``recv`` supports non-blocking
    polls for the ``real_time`` degradation mode (``btb/env.py:222-233``).
    """

    def __init__(self, bind_addr: str, codec: str = "tensor",
                 default_timeoutms: int = constants.DEFAULT_TIMEOUTMS,
                 allow_pickle: bool = True):
        self.codec = codec
        self.default_timeoutms = default_timeoutms
        self.allow_pickle = allow_pickle
        self.sock = zmq_context().socket(zmq.REP)
        self.sock.setsockopt(zmq.LINGER, 0)
        self.sock.bind(bind_addr)
        self.addr = self.sock.getsockopt_string(zmq.LAST_ENDPOINT)
        self._register_poller()

    def recv(self, timeoutms: int | None = None):
        """Receive one request, or ``None`` on timeout (``timeoutms=0`` polls)."""
        t = self.default_timeoutms if timeoutms is None else timeoutms
        out = self._poll_recv(t, copy_arrays=True)
        return None if out is None else out[0]

    def reply(self, **kwargs):
        self.sock.send_multipart(encode_message(kwargs, codec=self.codec), copy=True)

