"""2-process CPU rehearsal of the multi-host launch path (r4 verdict
item 5): ``distributed_init`` with an explicit coordinator, a global mesh
spanning both processes, and a real cross-process psum through
``linalg.gram`` — so the multi-host entry point is exercised code, not
dead code. Runbook: docs/MULTIHOST.md."""

import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_rehearsal():
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    # The rehearsal must work from a bare checkout too (a fresh machine
    # loses the editable install; sys.path[0] is scripts/, not the repo).
    env["PYTHONPATH"] = REPO + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(REPO, "scripts/multihost_rehearsal.py"),
             "--coordinator", f"127.0.0.1:{port}",
             "--num-hosts", "2", "--host-id", str(i),
             "--virtual-devices", "4"],
            cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        for i in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-2000:]
        assert "REHEARSAL_OK" in out, out[-2000:]
        # both processes must see the 8-device GLOBAL mesh (4 local each)
        assert "4 local / 8 global" in out, out[-2000:]


def test_partial_manual_config_raises(monkeypatch):
    """Half a manual-cluster config (host id without coordinator) must
    fail loudly, not silently degrade to an uncoordinated single host."""
    import pytest

    from keystone_tpu.parallel.mesh import distributed_init

    monkeypatch.delenv("KEYSTONE_COORDINATOR", raising=False)
    monkeypatch.setenv("KEYSTONE_NUM_HOSTS", "4")
    monkeypatch.setenv("KEYSTONE_HOST_ID", "1")
    with pytest.raises(ValueError, match="KEYSTONE_COORDINATOR"):
        distributed_init()
