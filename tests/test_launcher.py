"""Launcher tests against real spawned producer processes.

Reference model: ``tests/test_launcher.py`` (arg/seed/socket plumbing,
multi-machine via a second process, liveness). Uses the headless fake
producer instead of Blender.
"""

import multiprocessing as mp
import os
import sys
import time

import pytest

from blendjax.launcher import LaunchInfo, parse_launch_args
from blendjax.launcher.arguments import format_launch_args
from blendjax.launcher.launcher import PythonProducerLauncher
from blendjax.transport import DataReceiverSocket

PRODUCER = os.path.join(os.path.dirname(__file__), "producers", "echo_producer.py")


def test_arguments_roundtrip():
    argv = ["ignored", "stuff", "--"] + format_launch_args(
        3, 13, {"DATA": "tcp://127.0.0.1:11000", "CTRL": "tcp://127.0.0.1:11004"},
        extra=["--render-every", "10"],
    )
    args, remainder = parse_launch_args(argv)
    assert args.btid == 3 and args.btseed == 13
    assert args.btsockets == {
        "DATA": "tcp://127.0.0.1:11000",
        "CTRL": "tcp://127.0.0.1:11004",
    }
    assert remainder == ["--render-every", "10"]
    # alias properties
    assert args.instance_id == 3 and args.seed == 13 and args.sockets


def test_launch_two_instances_handshake(monkeypatch):
    """Two instances get distinct ids, seeds seed+i, distinct tcp addresses,
    and their per-instance extra args (reference ``test_launcher.py:20-44``)."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")  # what a trainer's env may say
    with PythonProducerLauncher(
        script=PRODUCER,
        num_instances=2,
        named_sockets=["DATA"],
        seed=10,
        instance_args=[["--x", "a"], ["--x", "b"]],
    ) as launcher:
        addrs = launcher.addresses["DATA"]
        assert len(addrs) == 2 and len(set(addrs)) == 2
        assert all(a.startswith("tcp://127.0.0.1:") for a in addrs)
        recv = DataReceiverSocket(addrs, timeoutms=10000)
        seen = {}
        while len(seen) < 2:
            msg, _ = recv.recv()
            seen[msg["btid"]] = msg
        recv.close()
    assert seen[0]["btseed"] == 10 and seen[1]["btseed"] == 11
    assert seen[0]["remainder"] == ["--x", "a"]
    assert seen[1]["remainder"] == ["--x", "b"]
    assert seen[0]["sockets"]["DATA"] == addrs[0]
    # a child of the process that holds the chip can never need it
    assert seen[0]["jax_platforms"] == seen[1]["jax_platforms"] == "cpu"


def test_assert_alive_and_teardown():
    with PythonProducerLauncher(script=PRODUCER, num_instances=1) as launcher:
        launcher.assert_alive()
        pid = launcher.processes[0].pid
    # context exit must have terminated the producer
    with pytest.raises(OSError):
        os.kill(pid, 0)


def test_dead_producer_detected():
    with PythonProducerLauncher(
        script="-c", script_args=["import sys; sys.exit(3)"], num_instances=1
    ) as launcher:
        # -c trick: argv becomes [python, -c, 'exit(3)', --, handshake...]
        # Interpreter startup can take a couple of seconds on small hosts.
        launcher.processes[0].wait(timeout=30)
        with pytest.raises(RuntimeError, match="died"):
            launcher.assert_alive()


def test_respawn_brings_producer_back():
    with PythonProducerLauncher(
        script=PRODUCER, num_instances=1, respawn=True
    ) as launcher:
        first = launcher.processes[0]
        first.terminate()
        first.wait()
        launcher.poll()
        launcher.assert_alive()
        assert launcher.processes[0].pid != first.pid


def _remote_launch(info_path, ready):
    from blendjax.launcher.launcher import PythonProducerLauncher

    with PythonProducerLauncher(script=PRODUCER, num_instances=1, seed=5) as ln:
        ln.launch_info.save_json(info_path)
        ready.set()
        ln.wait()


def test_two_machine_workflow_via_launch_info(tmp_path):
    """Launch in another process, connect via serialized LaunchInfo
    (reference ``test_launcher.py:47-91`` / ``apps/launch.py``)."""
    info_path = str(tmp_path / "launch_info.json")
    ready = mp.Event()
    proc = mp.Process(target=_remote_launch, args=(info_path, ready))
    proc.start()
    try:
        assert ready.wait(timeout=30)
        info = LaunchInfo.load_json(info_path)
        recv = DataReceiverSocket(info.addresses["DATA"], timeoutms=10000)
        msg, _ = recv.recv()
        assert msg["btid"] == 0 and msg["btseed"] == 5
        recv.close()
    finally:
        proc.terminate()
        proc.join(timeout=10)


def test_launch_info_roundtrip(tmp_path):
    info = LaunchInfo(
        addresses={"DATA": ["tcp://1.2.3.4:11000"]},
        commands=["blender ..."],
        processes=[123],
    )
    p = tmp_path / "li.json"
    info.save_json(str(p))
    back = LaunchInfo.load_json(str(p))
    assert back == info
    # file-object path (the reference's nullcontext bug regression test)
    import io

    buf = io.StringIO()
    info.save_json(buf)
    assert LaunchInfo.from_json(buf.getvalue()) == info


def test_cli_app_python_kind(tmp_path):
    """blendjax-launch with a python-producer config writes LaunchInfo."""
    import json
    import subprocess

    cfg = {
        "kind": "python",
        "script": PRODUCER,
        "num_instances": 1,
        "named_sockets": ["DATA"],
        "seed": 2,
    }
    cfg_path = tmp_path / "launch.json"
    cfg_path.write_text(json.dumps(cfg))
    out_path = tmp_path / "info.json"
    proc = subprocess.Popen(
        [sys.executable, "-m", "blendjax.launcher.apps", str(cfg_path),
         "--out", str(out_path)],
        cwd=str(tmp_path),
    )
    try:
        deadline = time.time() + 30
        while not out_path.exists() and time.time() < deadline:
            time.sleep(0.1)
        assert out_path.exists()
        info = LaunchInfo.load_json(str(out_path))
        recv = DataReceiverSocket(info.addresses["DATA"], timeoutms=10000)
        msg, _ = recv.recv()
        assert msg["btseed"] == 2
        recv.close()
    finally:
        proc.terminate()
        proc.wait(timeout=10)


@pytest.mark.skipif(sys.platform != "linux", reason="PDEATHSIG is Linux-only")
def test_producers_die_with_killed_launcher(tmp_path):
    """Orphan-proofing: SIGKILL the launcher process (its __exit__ never
    runs) and the kernel's parent-death signal must still reap the
    producer — a leaked producer loops forever and starves shared-core
    hosts."""
    import json
    import signal
    import subprocess
    import textwrap

    # The cube producer runs FOREVER without --frames, so the assertion
    # cannot pass vacuously by the producer exiting on its own (the echo
    # producer self-exits after ~10s, inside the polling window).
    forever = os.path.join(
        os.path.dirname(__file__), "..", "examples", "datagen",
        "cube_producer.py",
    )
    child_src = textwrap.dedent(
        """
        import json, os, time
        from blendjax.launcher import PythonProducerLauncher
        ln = PythonProducerLauncher(
            script=%r, num_instances=1, named_sockets=["DATA"], seed=0,
            instance_args=[["--shape", "32", "32"]],
        ).__enter__()
        print(json.dumps(ln.launch_info.processes), flush=True)
        time.sleep(60)  # parent SIGKILLs us; producer must die anyway
        """
        % forever
    )
    p = subprocess.Popen(
        [sys.executable, "-c", child_src], stdout=subprocess.PIPE, text=True
    )
    try:
        pids = json.loads(p.stdout.readline())
        os.kill(p.pid, signal.SIGKILL)
        p.wait(timeout=10)
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                os.kill(pids[0], 0)
            except ProcessLookupError:
                return  # reaped
            time.sleep(0.2)
        os.kill(pids[0], signal.SIGKILL)  # clean up before failing
        pytest.fail("producer outlived its SIGKILLed launcher")
    finally:
        if p.poll() is None:
            p.kill()


def test_wait_does_not_hold_the_membership_lock():
    """BJX117/BJX119 regression: wait() snapshots under the lock but
    blocks OUTSIDE it, so a fleet controller can still poll/scale while
    the owner waits for the fleet to exit."""
    import sys as _sys
    import threading

    from blendjax.launcher import ProcessLauncher

    def command(i, handshake):
        return [_sys.executable, "-c", "import time; time.sleep(30)"] + handshake

    with ProcessLauncher(command, num_instances=1,
                         named_sockets=["DATA"]) as ln:
        done = threading.Event()
        codes = []

        def waiter():
            codes.append(ln.wait())
            done.set()

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        # while wait() blocks on the child, the membership surface must
        # stay available (pre-fix this deadlocked until the child died)
        for _ in range(5):
            assert ln.poll_processes() == [None]
            assert ln.active_indices() == [0]
        ln.retire_instance(0, drain=False)
        assert done.wait(10.0), "wait() never returned after the kill"
        assert codes and codes[0][0] is not None
