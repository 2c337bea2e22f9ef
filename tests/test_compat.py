"""The host helpers in ``repro.compat``: the mesh over the devices that
exist, the CPU-only re-exec, and where the compile cache goes."""
import os

import jax
import pytest
from jax.sharding import AxisType

from repro import compat


def test_mesh_is_auto_over_default_backend_devices():
    mesh = compat.mesh(1, "shard")
    assert mesh.axis_names == ("shard",)
    assert mesh.axis_types == (AxisType.Auto,)
    assert list(mesh.devices.flat) == jax.devices()[:1]


def test_mesh_raises_when_too_few_devices():
    with pytest.raises(RuntimeError, match="needs"):
        compat.mesh(len(jax.devices()) + 1)


class _Exec(Exception):
    pass


@pytest.fixture
def execs(monkeypatch):
    """Record would-be re-execs instead of replacing the test process."""
    calls = []

    def fake(path, argv, env):
        calls.append(env)
        raise _Exec
    monkeypatch.setattr(os, "execve", fake)
    return calls


@pytest.mark.parametrize("platforms", ["", "tpu", "cpu,tpu"])
def test_ensure_host_devices_does_nothing_off_a_cpu_run(
        platforms, execs, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    compat.ensure_host_devices(8)
    assert execs == []


@pytest.mark.parametrize("flags,reexec", [
    ("", True),
    ("--xla_force_host_platform_device_count=4", True),
    ("--xla_force_host_platform_device_count=8", False),
    ("--foo --xla_force_host_platform_device_count=16", False),
])
def test_ensure_host_devices_reexecs_a_cpu_run_only_when_short(
        flags, reexec, execs, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", flags)
    if reexec:
        with pytest.raises(_Exec):
            compat.ensure_host_devices(8)
        assert execs[0]["XLA_FLAGS"].endswith(
            "--xla_force_host_platform_device_count=8")
        assert execs[0]["JAX_PLATFORMS"] == "cpu"
    else:
        compat.ensure_host_devices(8)
        assert execs == []


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_the_environment(cache_config, monkeypatch,
                                               tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compat.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_checkout_path(cache_config,
                                                         monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compat.use_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
