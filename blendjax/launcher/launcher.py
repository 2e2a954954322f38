"""Producer fleet launcher.

Reference: ``pkg_pytorch/blendtorch/btt/launcher.py:15-197``. Same contract
— a context manager that allocates one address per (named socket x
instance), derives per-instance seeds ``seed+i``, spawns each producer in
its own process group with the CLI handshake appended after ``--``, polls
liveness, and kills everything on exit — generalized beyond Blender:

- :class:`ProcessLauncher` spawns any command template, so headless
  simulation producers (tests, benchmarks; SURVEY.md §4 "fake producer")
  and Blender use one code path.
- Optional ``respawn`` brings dead producers back (the data stream is
  stateless DP, so restart is safe); the reference is strictly fail-fast
  (``launcher.py:166-171``) and that remains the default.
- Note: the reference computed popen kwargs but passed a stale variable
  (``launcher.py:126-132``, latent bug) — not reproduced here.
"""

from __future__ import annotations

import os
import signal
import socket as pysocket
import subprocess
import sys
import tempfile
import threading
import time

from blendjax.launcher.arguments import format_launch_args
from blendjax.launcher.launch_info import LaunchInfo
from blendjax.transport.shm import REGISTRY_ENV as SHM_REGISTRY_ENV
from blendjax.transport.shm import reap_registry
from blendjax.utils.ipaddr import get_primary_ip
from blendjax.utils.logging import get_logger
from blendjax.utils.tg import guard

# Read-only container surface left unguarded on the membership tables:
# tests and observers read a quiesced fleet from any thread; every
# MUTATION (append, setitem, add, clear) still demands `_lock`.
_MEMBER_READS = (
    "__getitem__", "__iter__", "__len__", "__contains__",
    "index", "count", "copy",
)

logger = get_logger("launcher")

# Every producer ever spawned by this process (Popen objects; exited
# ones stay harmlessly in the list). Emergency teardown for callers
# that must abandon a stuck session without running context-manager
# exits — e.g. a benchmark watchdog bailing out of a hard device
# stall via os._exit, where spawns from worker threads carry no
# PDEATHSIG and would otherwise orphan onto the shared core forever.
_ALL_SPAWNED: list = []


def kill_all_spawned() -> None:
    """SIGKILL every still-running spawned producer (by process group:
    each spawn starts its own session). Sweeps until the registry stops
    growing: a concurrently-unsticking worker thread may spawn a new
    producer mid-sweep, which would otherwise slip through."""
    swept = 0
    while True:
        snapshot = list(_ALL_SPAWNED)
        if len(snapshot) <= swept:
            return
        for proc in snapshot[swept:]:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
        swept = len(snapshot)

# PDEATHSIG orphan-proofing is Linux-only (prctl(2)). It is applied via
# an exec-shim — a fresh single-threaded python that sets the flag on
# ITSELF then execs the producer in place (same PID) — never via
# preexec_fn: a Python-level hook between fork and exec is documented
# fork-unsafe in threaded parents (jax/zmq threads are typically live)
# and disables subprocess's posix_spawn fast path.
# Interpreter startup is tens of ms — a launcher killed in that window
# died BEFORE the prctl armed. Re-checking the parent after arming
# closes the race: either the launcher is still our parent (and its
# death now signals us), or it already died (we were reparented) and we
# exit instead of exec'ing an orphan. A failing prctl (non-glibc libc,
# missing symbol) degrades to launching without orphan-proofing, same
# as the non-Linux path (SystemExit passes through the except).
_PDEATHSIG_SHIM = """\
import os, sys
try:
    import ctypes
    ctypes.CDLL(None).prctl(1, 15)  # PR_SET_PDEATHSIG, SIGTERM
    if os.getppid() != int(sys.argv[1]):
        sys.exit(143)
except Exception:
    pass
os.execvp(sys.argv[2], sys.argv[2:])
"""


def _free_port(host: str) -> int:
    """Probe a free TCP port by binding port 0 (small race window; fine for
    single-host use — fixed ``start_port`` mode exists for multi-machine)."""
    with pysocket.socket(pysocket.AF_INET, pysocket.SOCK_STREAM) as s:
        s.setsockopt(pysocket.SOL_SOCKET, pysocket.SO_REUSEADDR, 1)
        s.bind((host, 0))
        return s.getsockname()[1]


# bjx: thread-shared (the fleet controller's control thread scales the
# membership while the owner polls/retires: `_lock` guards it — BJX117)
class ProcessLauncher:
    """Launch ``num_instances`` producer processes speaking the handshake.

    Parameters mirror the reference's ``BlenderLauncher`` (``launcher.py:
    58-70``): ``named_sockets`` get one ``tcp://`` address per instance,
    ``seed`` derives per-instance seeds ``seed+i`` (``launcher.py:109-112``),
    ``instance_args`` appends per-instance user flags, ``bind_addr`` may be
    ``'primaryip'`` to expose producers to other machines
    (``launcher.py:187-188``).

    ``command`` is a callable ``(instance_index, handshake_argv) ->
    list[str]`` producing the full argv for one instance.

    Elastic membership (the fleet controller's substrate): after
    ``__enter__`` the fleet can grow and shrink at runtime —
    :meth:`add_instance` allocates a fresh address per named socket,
    continues the per-instance seed ladder (``seed + i``), and retries
    allocation when the probed port is stolen before the producer
    binds; :meth:`retire_instance` drains an instance gracefully
    (SIGTERM, bounded wait for a clean exit so the producer's linger
    flush delivers its tail) before killing; :meth:`scale_to` composes
    the two. Retired slots stay in place so instance indices (== btids)
    remain stable for lineage and respawn. All membership mutations are
    serialized by one reentrant lock, so a controller thread and a
    pipeline's timeout health-check can't interleave.
    """

    #: add_instance retries with FRESH addresses when the producer dies
    #: within the bind grace window (free-port probe race: the probed
    #: port can be stolen between probe-close and producer bind).
    BIND_RETRIES = 3

    def __init__(
        self,
        command,
        num_instances: int = 1,
        named_sockets=("DATA",),
        seed: int = 0,
        bind_addr: str = "127.0.0.1",
        start_port: int | None = None,
        instance_args=None,
        respawn: bool = False,
        proto: str = "tcp",
        bind_grace_s: float = 2.0,
    ):
        assert num_instances > 0, "need at least one instance"
        self.command = command
        self.num_instances = num_instances
        self.named_sockets = list(named_sockets)
        self.seed = seed
        self.instance_args = instance_args or [[] for _ in range(num_instances)]
        assert len(self.instance_args) == num_instances
        self.respawn = respawn
        self.proto = proto
        self.bind_addr = (
            get_primary_ip() if bind_addr == "primaryip" else bind_addr
        )
        self.start_port = start_port
        self.bind_grace_s = float(bind_grace_s)
        self._lock = threading.RLock()
        # threadguard wiring: the membership tables may only be touched
        # under `_lock` (the contract the fleet controller's control
        # thread relies on — BJX117); guard() is identity unless
        # BLENDJAX_THREADGUARD=1.
        # read-only list surface exempt: tests and callers index a
        # quiesced fleet from the main thread; mutation stays locked
        self.processes: list = guard(
            [], name="launcher.processes", lock=self._lock,
            exempt=_MEMBER_READS,
        )
        self.launch_info: LaunchInfo | None = None
        self._argvs: list = []
        self._ipc_dir: str | None = None
        self._shm_registry: str | None = None
        self._retired: set = guard(
            set(), name="launcher.retired", lock=self._lock,
            exempt=_MEMBER_READS,
        )
        self._next_port: int | None = None

    # -- address plan -------------------------------------------------------

    def _allocate_addresses(self) -> dict:
        """One address per (socket name x instance): ``{name: [addr, ...]}``.

        With ``start_port`` set, ports are deterministic ``start_port+k``
        in socket-major order (reference starts at 11000,
        ``launcher.py:63,104-107``); otherwise free ports are probed.
        ``proto='ipc'`` uses unix-socket endpoints instead — cheaper than
        TCP loopback for same-host producer fleets.
        """
        addresses: dict = {}
        if self.proto == "ipc":
            base = self._ipc_dir = tempfile.mkdtemp(prefix="blendjax-ipc-")
            return {
                name: [
                    f"ipc://{base}/{name}-{i}"
                    for i in range(self.num_instances)
                ]
                for name in self.named_sockets
            }
        port = self.start_port
        for name in self.named_sockets:
            addrs = []
            for _ in range(self.num_instances):
                if port is not None:
                    p, port = port, port + 1
                else:
                    p = _free_port(self.bind_addr)
                addrs.append(f"{self.proto}://{self.bind_addr}:{p}")
            addresses[name] = addrs
        # incremental scaling continues the deterministic ladder here
        self._next_port = port
        return addresses

    def _instance_addresses(self, index: int) -> dict:
        """A fresh ``{name: addr}`` set for one NEW instance (the
        incremental counterpart of :meth:`_allocate_addresses`)."""
        if self.proto == "ipc":
            assert self._ipc_dir is not None, "not launched"
            return {
                name: f"ipc://{self._ipc_dir}/{name}-{index}"
                for name in self.named_sockets
            }
        sockets = {}
        for name in self.named_sockets:
            if self._next_port is not None:
                p, self._next_port = self._next_port, self._next_port + 1
            else:
                p = _free_port(self.bind_addr)
            sockets[name] = f"{self.proto}://{self.bind_addr}:{p}"
        return sockets

    # -- lifecycle ----------------------------------------------------------

    def _instance_argv(self, i: int, sockets: dict, extra=None) -> list:
        handshake = ["--"] + format_launch_args(
            btid=i,
            btseed=self.seed + i,
            btsockets=sockets,
            extra=self.instance_args[i] if extra is None else extra,
        )
        return self.command(i, handshake)

    def __enter__(self) -> "ProcessLauncher":
        # Under the membership lock like every other membership writer:
        # a fleet controller attached early must observe either the
        # pre-launch or the fully-launched fleet, never a half-built
        # processes/launch_info pair (BJX117).
        with self._lock:
            addresses = self._allocate_addresses()
            self._argvs = []
            try:
                for i in range(self.num_instances):
                    sockets = {n: addresses[n][i] for n in self.named_sockets}
                    argv = self._instance_argv(i, sockets)
                    self._argvs.append(argv)
                    self.processes.append(self._spawn(argv))
                    logger.info(
                        "launched instance %d: %s", i, " ".join(map(str, argv))
                    )
            except BaseException:
                # __exit__ never runs when __enter__ raises; reap what we
                # already spawned before propagating.
                self.__exit__(None, None, None)
                raise
            self.launch_info = LaunchInfo(
                addresses=addresses,
                commands=[" ".join(map(str, a)) for a in self._argvs],
                processes=[p.pid for p in self.processes],
            )
            return self

    def _spawn(self, argv):
        # Own session/process group so the whole producer tree can be
        # signalled together (reference launches in a new process group,
        # ``launcher.py:124-132``). Producer scripts import blendjax; make
        # the package root importable in the child even when blendjax runs
        # from a source checkout rather than site-packages (subprocess
        # sys.path[0] is the script dir, not our cwd).
        env = dict(os.environ)
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        parts = [pkg_root] + [
            p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p
        ]
        env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
        # An accelerator belongs to one process, and that is the trainer
        # that launches us. Producers are JAX-free by design; should one
        # import JAX anyway, it must not reach for the parent's chip.
        env["JAX_PLATFORMS"] = "cpu"

        # Shared-memory segment lifecycle (blendjax.transport.shm): the
        # launcher owns the unlink for instances it spawned. Producers
        # that create an ShmRing register it (one marker file per
        # segment) in this directory; retire_instance reaps that
        # instance's segments after the kill, __exit__ reaps the rest —
        # so segments are unlinked exactly once even when a producer is
        # SIGKILLed mid-write.
        if self._shm_registry is None:
            self._shm_registry = tempfile.mkdtemp(prefix="blendjax-shm-")
        env[SHM_REGISTRY_ENV] = self._shm_registry

        # Orphan-proofing (Linux): if the launcher dies without its
        # __exit__ running (SIGKILL, `timeout`), the kernel delivers
        # SIGTERM to the producer — otherwise a leaked producer loops
        # forever and starves shared-core hosts. The _PDEATHSIG_SHIM
        # exec's the real argv in place, so Popen's pid IS the
        # producer's and poll/terminate semantics are unchanged; the
        # microsecond pre-prctl window is the only coverage lost vs a
        # preexec hook, traded for a fork that runs no Python at all.
        # PDEATHSIG fires on the death of the spawning THREAD
        # (prctl(2)), so the shim wraps only main-thread spawns — a
        # producer respawned from a pipeline's ingest thread must not
        # die with that thread; it falls back to context-manager
        # teardown. setsid stays C-level via start_new_session.
        if (
            sys.platform == "linux"
            and threading.current_thread() is threading.main_thread()
        ):
            import shutil

            # The shim's Popen always succeeds (it execs python), which
            # would swallow the FileNotFoundError a bad producer command
            # raises on the direct path — keep that contract by checking
            # the real target up front.
            # Resolve against the PATH the shim's execvp will actually
            # use (the env dict's), not the launcher's own.
            exe = str(argv[0])
            if shutil.which(exe, path=env.get("PATH", os.defpath)) is None:
                raise FileNotFoundError(
                    f"producer command not found or not executable: {exe!r}"
                )
            # -S -E: the shim imports only os/sys/ctypes, and skipping
            # site/user-site startup shrinks the pre-prctl orphan window
            # (the env dict still reaches the exec'd producer untouched).
            argv = [
                sys.executable, "-S", "-E", "-c", _PDEATHSIG_SHIM,
                str(os.getpid()), *map(str, argv),
            ]
        proc = subprocess.Popen(argv, start_new_session=True, env=env)
        _ALL_SPAWNED.append(proc)
        return proc

    @property
    def addresses(self) -> dict:
        with self._lock:
            assert self.launch_info is not None, "not launched"
            return self.launch_info.addresses

    def poll(self) -> list:
        """Return per-instance exit codes (None = running); with
        ``respawn=True`` dead non-retired instances are relaunched
        first. Retired slots report their exit code and are never
        respawned."""
        with self._lock:
            codes = [p.poll() for p in self.processes]
            if self.respawn:
                for i, code in enumerate(codes):
                    if code is not None and i not in self._retired:
                        logger.warning(
                            "instance %d exited with %s; respawning", i, code
                        )
                        self.processes[i] = self._spawn(self._argvs[i])
                        codes[i] = None
            return codes

    def poll_processes(self) -> list:
        """Per-instance exit codes with NO respawn side effect — the
        fleet controller's liveness read (it owns the respawn decision
        via :meth:`respawn_instance`)."""
        with self._lock:
            return [p.poll() for p in self.processes]

    def assert_alive(self) -> None:
        """Raise if any non-retired instance died (reference
        ``launcher.py:166-171``)."""
        with self._lock:
            if not self.processes:
                return
            codes = self.poll()
            dead = {
                i: c for i, c in enumerate(codes)
                if c is not None and i not in self._retired
            }
        if dead:
            raise RuntimeError(f"producer instances died (id: exitcode) {dead}")

    def wait(self) -> list:
        """Block until all instances exit; returns exit codes
        (reference ``launcher.py:173-175``). The membership snapshot is
        taken under the lock but the waits run OUTSIDE it — holding
        ``_lock`` across an unbounded ``p.wait()`` would block every
        fleet-controller poll/scale call until the fleet exits
        (BJX117/BJX119)."""
        with self._lock:
            procs = list(self.processes)
        return [p.wait() for p in procs]

    # -- elastic membership --------------------------------------------------

    @property
    def retired(self) -> frozenset:
        with self._lock:
            return frozenset(self._retired)

    def active_indices(self) -> list:
        """Instance indices currently part of the fleet (not retired);
        momentarily-dead instances count — they are respawn material,
        not departures."""
        with self._lock:
            return [
                i for i in range(len(self.processes))
                if i not in self._retired
            ]

    def active_count(self) -> int:
        return len(self.active_indices())

    def instance_sockets(self, i: int) -> dict:
        """``{socket_name: addr}`` of one instance."""
        with self._lock:
            assert self.launch_info is not None, "not launched"
            return {
                n: self.launch_info.addresses[n][i] for n in self.named_sockets
            }

    def _watch_bind(self, proc, grace_s: float):
        """Poll a fresh spawn through the bind window; returns its exit
        code if it died within ``grace_s`` (bind failure signature),
        None if it is still running."""
        deadline = time.monotonic() + max(0.0, grace_s)
        while True:
            code = proc.poll()
            if code is not None:
                return code
            if time.monotonic() >= deadline:
                return None
            time.sleep(0.05)

    def add_instance(self, extra_args=None, bind_grace_s: float | None = None):
        """Grow the fleet by one instance; returns ``(index, sockets)``.

        The new instance gets the next btid/seed on the ladder and a
        fresh address per named socket. ``extra_args=None`` INHERITS
        the highest active instance's args (a scale-up must match the
        running fleet's shape/encoding config, or the consumer's
        decoder meets mismatched frames mid-run); pass ``[]``
        explicitly for a bare instance. The free-port probe is
        inherently racy (the port is probed-then-closed before the
        producer binds), and incremental scaling allocates one port at
        a time — so a spawn that dies within the bind grace window is
        retried up to ``BIND_RETRIES`` times with NEWLY probed
        addresses instead of failing the scale-up. Deterministic
        (``start_port``) and ipc address plans are not re-probed: an
        early death there is a real producer failure.
        """
        with self._lock:
            assert self.launch_info is not None, "not launched"
            i = self.num_instances
            grace = self.bind_grace_s if bind_grace_s is None else bind_grace_s
            if extra_args is None:
                active = self.active_indices()
                extra_args = self.instance_args[active[-1]] if active else []
            args = [str(a) for a in extra_args]
            reprobe = self.start_port is None and self.proto != "ipc"
            attempts = (self.BIND_RETRIES + 1) if reprobe else 1
            last_code = None
            for attempt in range(attempts):
                sockets = self._instance_addresses(i)
                argv = self._instance_argv(i, sockets, extra=args)
                proc = self._spawn(argv)
                code = self._watch_bind(proc, grace)
                if code is None:
                    self.num_instances += 1
                    self.instance_args.append(args)
                    self._argvs.append(argv)
                    self.processes.append(proc)
                    for name in self.named_sockets:
                        self.launch_info.addresses[name].append(sockets[name])
                    self.launch_info.commands.append(
                        " ".join(map(str, argv))
                    )
                    self.launch_info.processes.append(proc.pid)
                    logger.info(
                        "added instance %d (attempt %d): %s",
                        i, attempt + 1, " ".join(map(str, argv)),
                    )
                    return i, sockets
                last_code = code
                if attempt + 1 < attempts:
                    logger.warning(
                        "instance %d died with %s within %.1fs of launch "
                        "(probed port likely stolen before bind); retrying "
                        "with fresh addresses", i, code, grace,
                    )
            raise RuntimeError(
                f"instance {i} failed to come up "
                f"({attempts} attempt(s), last exit code {last_code})"
            )

    def retire_instance(self, i: int, drain: bool = True,
                        timeout: float = 5.0) -> dict:
        """Remove instance ``i`` from the fleet; returns its sockets.

        ``drain=True`` sends SIGTERM to the process group and waits up
        to ``timeout`` for a clean exit — a producer with a graceful
        TERM handler flushes its publish queue on the way out
        (``term_context``), so in-flight frames reach the consumer
        instead of dying in the send queue. Only then (or with
        ``drain=False``, immediately) is the group SIGKILLed. The slot
        stays in place (indices == btids stay stable); ``poll``/
        ``assert_alive``/respawn skip it from now on.
        """
        with self._lock:
            if not (0 <= i < len(self.processes)):
                raise IndexError(f"no instance {i}")
            if i in self._retired:
                return self.instance_sockets(i)
            self._retired.add(i)
            proc = self.processes[i]
            sockets = self.instance_sockets(i)
            shm_registry = self._shm_registry
        if proc.poll() is None:
            if drain:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
                try:
                    proc.wait(timeout=timeout)
                except subprocess.TimeoutExpired:
                    logger.warning(
                        "instance %d did not drain within %.1fs; killing",
                        i, timeout,
                    )
            if proc.poll() is None:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                try:
                    proc.wait(timeout=timeout)
                except subprocess.TimeoutExpired:
                    pass
        # The launcher owns the unlink for segments this instance
        # registered (btid == index): reaped only after the process is
        # gone, so a drain's in-flight descriptors stayed readable.
        # reap_registry removes each marker file with its segment, so
        # racing the teardown reap stays exactly-once.
        if shm_registry is not None:
            reap_registry(shm_registry, btid=i)
        logger.info("retired instance %d (%s)", i, sockets)
        return sockets

    def respawn_instance(self, i: int):
        """Relaunch a dead instance in place (same argv, same btid —
        the consumer's lineage reads the fresh seq numbering as a
        producer RESTART, not a drop storm). The fleet controller's
        explicit counterpart of ``respawn=True``."""
        with self._lock:
            if i in self._retired:
                raise ValueError(f"instance {i} is retired")
            if self.processes[i].poll() is None:
                return self.processes[i]
            # the dead producer's segments are unreadable going forward
            # (fresh spawn creates a fresh ring); reap them now so
            # respawn churn can't accumulate /dev/shm leaks
            if self._shm_registry is not None:
                reap_registry(self._shm_registry, btid=i)
            proc = self._spawn(self._argvs[i])
            self.processes[i] = proc
            self.launch_info.processes[i] = proc.pid
            logger.warning("respawned instance %d (pid %d)", i, proc.pid)
            return proc

    def scale_to(self, n: int, extra_args=None):
        """Grow/shrink the active fleet to ``n`` instances; returns
        ``(added, removed)`` as lists of ``(index, sockets)``. Shrinks
        retire the highest-index active instances (with drain); growth
        goes through :meth:`add_instance`'s retrying allocation. NOTE:
        runs subprocess lifecycle (blocking waits) — call from a
        control thread, never from an ingest/draw hot path (BJX110)."""
        assert n >= 0
        added, removed = [], []
        with self._lock:
            while self.active_count() < n:
                added.append(self.add_instance(extra_args=extra_args))
            while self.active_count() > n:
                victim = self.active_indices()[-1]
                removed.append(
                    (victim, self.retire_instance(victim, drain=True))
                )
        return added, removed

    def __exit__(self, exc_type=None, exc=None, tb=None) -> bool:
        # Teardown owns the membership for its (bounded) duration: a
        # controller tick racing the final reap must see either the
        # live fleet or the emptied one (BJX117). Every wait below is
        # timeout-bounded, so the hold is finite.
        with self._lock:
            return self._exit_locked(exc_type)

    def _exit_locked(self, exc_type) -> bool:
        for p in self.processes:
            if p.poll() is None:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        for p in self.processes:
            try:
                p.wait(timeout=5.0)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(p.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                try:
                    p.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    # Unkillable (e.g. D-state) child; fall through to the
                    # liveness assert rather than masking the original error.
                    pass
        # All children must be gone (reference asserts, ``launcher.py:181``).
        still = [p.pid for p in self.processes if p.poll() is None]
        # re-guard on rebind: the emptied tables keep the lock contract
        self.processes = guard(
            [], name="launcher.processes", lock=self._lock,
            exempt=_MEMBER_READS,
        )
        self._retired = guard(
            set(), name="launcher.retired", lock=self._lock,
            exempt=_MEMBER_READS,
        )
        if self._ipc_dir is not None:
            # SIGTERM'd producers never unlink their unix sockets; stale
            # files would also break rebinding after a respawn.
            import shutil

            shutil.rmtree(self._ipc_dir, ignore_errors=True)
            self._ipc_dir = None
        if self._shm_registry is not None:
            # every child is dead: unlink whatever segments remain
            # registered (retire_instance already reaped its own), then
            # drop the registry dir itself
            import shutil

            reap_registry(self._shm_registry)
            shutil.rmtree(self._shm_registry, ignore_errors=True)
            self._shm_registry = None
        if still:
            # Never mask an in-flight exception with the leak report.
            if exc_type is None:
                raise RuntimeError(
                    f"producers still alive after teardown: {still}"
                )
            logger.error("producers still alive after teardown: %s", still)
        else:
            logger.info("all producer instances terminated")
        return False


class PythonProducerLauncher(ProcessLauncher):
    """Launch headless Python producers (``python script -- handshake``) —
    the hermetic stand-in for Blender in tests/benchmarks (SURVEY.md §4)."""

    def __init__(self, script: str, script_args=None, **kwargs):
        self.script = script
        self.script_args = [str(a) for a in (script_args or [])]
        super().__init__(command=self._build, **kwargs)

    def _build(self, index, handshake):
        return [sys.executable, self.script, *self.script_args, *handshake]


class BlenderLauncher(ProcessLauncher):
    """Launch Blender instances running a scene + producer script.

    Reference: ``launcher.py:15-164``. Command shape preserved:
    ``blender <scene> [--background] --python-use-system-env --python
    <script> -- <handshake>`` so unmodified ``*.blend.py`` producer scripts
    work against a blendjax consumer.
    """

    def __init__(
        self,
        scene: str = "",
        script: str = "",
        background: bool = False,
        blend_path=None,
        **kwargs,
    ):
        from blendjax.launcher.finder import discover_blender

        self.blender_info = discover_blender(blend_path)
        if self.blender_info is None:
            raise FileNotFoundError(
                "no usable Blender found; install Blender and its producer "
                "deps, or use PythonProducerLauncher for headless producers"
            )
        self.scene = str(scene)
        self.script = str(script)
        self.background = background
        super().__init__(command=self._build, **kwargs)

    def _build(self, index, handshake):
        argv = [self.blender_info["path"]]
        if self.scene:
            argv.append(self.scene)
        if self.background:
            argv.append("--background")
        argv += ["--python-use-system-env", "--python", self.script]
        return argv + handshake
