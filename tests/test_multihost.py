"""Multi-host backend test: a REAL 2-process jax cluster on CPU
(`jax.distributed.initialize` + cross-process global arrays + a
collective), exercising parallel/multihost.py the way a pod entrypoint
does — the reference's NCCL/DeepSpeed story is empty stubs, so this is
the distributed-backend evidence (SURVEY.md §5.8).

Spawned as subprocesses because a cluster cannot share this pytest
process's already-initialized single-process backend.
"""

import os
import socket
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_multihost_child.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _scrubbed_env():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)   # child sets its own
    env.pop("XLA_FLAGS", None)
    return env


class TestTwoProcessCluster:
    def test_global_array_and_cross_host_reduction(self):
        n = 2
        addr = f"localhost:{_free_port()}"
        procs = [
            subprocess.Popen(
                [sys.executable, _CHILD, str(i), str(n), addr],
                env=_scrubbed_env(), cwd=_REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for i in range(n)
        ]
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=180)
                outs.append((p.returncode, out, err))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        for rc, out, err in outs:
            assert rc == 0, f"child failed (rc={rc}):\n{err[-2000:]}"
        # sum(arange(32)) — every host must see the global total
        for rc, out, err in outs:
            assert "SUM 496.0" in out, (out, err[-500:])


def test_initialize_noop_single_process():
    """initialize() with no coordinator info is a documented no-op (local
    runs and tests) — it must not touch the existing backend."""
    from alphafold2_tpu.parallel import multihost

    assert multihost.initialize() is False


@pytest.mark.quick
def test_package_import_does_not_initialize_backend():
    """The pod contract: `import alphafold2_tpu` then
    multihost.initialize() must work, so the package import may not
    initialize an XLA backend. Checked in a clean subprocess (this
    pytest process initialized its backend long ago)."""
    code = (
        "from jax._src import xla_bridge\n"
        "import alphafold2_tpu\n"
        "import alphafold2_tpu.parallel.multihost\n"
        "import alphafold2_tpu.data, alphafold2_tpu.config\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print('import-clean')\n")
    env = _scrubbed_env()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=_REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "import-clean" in proc.stdout
