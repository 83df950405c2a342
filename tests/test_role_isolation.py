"""One process for the chip: which roles may touch a JAX backend.

A chip belongs to one process. With the README's three commands the
coordinator starts first; until ISSUE 22 it (and the SDK, and the CLI)
initialised a backend at import, took the chip, and left the store on the
CPU without a word. Technique: run the role with a JAX_PLATFORMS that
names no real backend — any backend initialisation raises, so a role that
comes up and answers there provably never touched one.
"""

import os
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_BACKEND = "no_such_backend"

_SDK_DRIVE = r"""
import sys, time
from dingo_tpu.client import DingoClient
from dingo_tpu.server import pb

client = DingoClient(sys.argv[1], {})
deadline = time.monotonic() + 60
while True:
    try:
        client.coordinator.Hello(pb.HelloRequest())
        break
    except Exception:
        if time.monotonic() > deadline:
            raise
        time.sleep(0.2)
# a store announces itself (the heartbeat path rolls up metrics, capacity
# and events on the coordinator), then a region is placed on it
client.coordinator.StoreHeartbeat(pb.StoreHeartbeatRequest(store_id="s0"))
d = client.create_index_region(1, 0, 1 << 20, pb.VectorIndexParameter(
    index_type=pb.VECTOR_INDEX_TYPE_IVF_FLAT, dimension=768,
    metric_type=pb.METRIC_TYPE_L2, ncentroids=1024), replication=1)
assert d.peers == ["s0"], d
assert client.coordinator.Hello(pb.HelloRequest()).region_count == 1
import dingo_tpu.client.cli  # the CLI's import chain too
from jax._src import xla_bridge
assert not xla_bridge._backends, xla_bridge._backends
print("SDK_OK")
"""


def _env(**overrides):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for k, v in overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_coordinator_and_sdk_never_initialise_a_backend():
    port = _free_port()
    env = _env(JAX_PLATFORMS=NO_BACKEND)
    coord = subprocess.Popen(
        [sys.executable, "-m", "dingo_tpu.server.main", "--role",
         "coordinator", "--port", str(port), "--replication", "1"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        sdk = subprocess.run(
            [sys.executable, "-c", _SDK_DRIVE, f"127.0.0.1:{port}"],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
        assert sdk.returncode == 0, sdk.stdout + sdk.stderr
        assert "SDK_OK" in sdk.stdout
        assert coord.poll() is None, "the coordinator died"
    finally:
        coord.terminate()
        out, _ = coord.communicate(timeout=30)
    assert coord.returncode == 0, out
    assert "Traceback" not in out, out


@pytest.mark.parametrize("platforms", [NO_BACKEND, None])
def test_store_role_refuses_to_start_without_a_tpu(platforms):
    """A backend that does not exist, and no JAX_PLATFORMS at all (jax
    then falls back to the CPU when no TPU answers): either way the store
    exits at once, non-zero, saying which platform it wanted. Only an
    explicit JAX_PLATFORMS=cpu — what this suite runs under — serves from
    the CPU."""
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, "-m", "dingo_tpu.server.main", "--role", "store",
         "--id", "s0", "--port", str(_free_port())],
        env=_env(JAX_PLATFORMS=platforms), cwd=REPO, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert f"JAX_PLATFORMS={platforms or ''!r}" in p.stderr, p.stderr
    assert "listening" not in p.stdout
    assert time.monotonic() - t0 < 60


def test_compile_cache_directory(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set -> jax reads it, the code sets no
    directory; unset -> <checkout>/.jax_cache."""
    import jax

    from dingo_tpu.common import config

    set_in_code = []
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: set_in_code.append((name, value)))
    monkeypatch.setattr(config.os, "makedirs", lambda *a, **kw: None)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert config.enable_compile_cache() == str(tmp_path)
    assert set_in_code == []

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert config.enable_compile_cache() == want
    assert set_in_code == [("jax_compilation_cache_dir", want)]
