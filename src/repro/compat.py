"""Devices, meshes and the compile cache: one place for what depends on the
host the engine runs on.

* :func:`mesh` — the memory-server mesh over the devices that exist: the
  chips on a TPU host, forced host devices in a CPU run.
* :func:`ensure_host_devices` — gives a CPU run (``JAX_PLATFORMS=cpu``) the
  host devices a script's mesh needs; does nothing anywhere else.
* :func:`use_compile_cache` — JAX's persistent compilation cache, placed by
  ``JAX_COMPILATION_CACHE_DIR`` or at a fixed path in the checkout.
"""
from __future__ import annotations

import os
import re
import sys

import jax
from jax.sharding import AxisType, Mesh

# <checkout>/.jax_cache: fixed, so the cache key (which includes the path)
# stays the same from run to run; listed in .gitignore
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def mesh(n: int, axis: str = "mem") -> Mesh:
    """A 1-D mesh over the first ``n`` devices of the default backend.

    Each device is one memory server. The axis is ``Auto``: under the
    ``Explicit`` axes that ``jax.make_mesh`` gives by default, gathers on
    arrays sharded over the axis raise ``ShardingTypeError``. Raises if the
    backend has fewer than ``n`` devices.
    """
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"a {n}-device mesh needs {n} devices; the "
            f"{jax.default_backend()} backend has {len(devices)} (a CPU run "
            f"gets more with JAX_PLATFORMS=cpu and "
            f"compat.ensure_host_devices, or XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n})")
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,),
                         devices=devices[:n])


def ensure_host_devices(n: int) -> None:
    """Give a CPU run ``n`` host devices for a script's simulated mesh.

    Decided from the environment alone, without starting a backend: only
    under ``JAX_PLATFORMS=cpu``, and only when ``XLA_FLAGS`` does not
    already force ``n`` or more host devices, does the script re-exec
    itself with the flag added (XLA reads it once, when the CPU backend
    starts). No accelerator has been touched in such a run. On any other
    platform it does nothing: the mesh takes the accelerator's devices and
    :func:`mesh` raises if there are too few.
    """
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    forced = re.findall(r"--xla_force_host_platform_device_count=(\d+)",
                        flags)
    if forced and int(forced[-1]) >= n:
        return
    env = dict(os.environ, XLA_FLAGS=(
        flags + f" --xla_force_host_platform_device_count={n}").strip())
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX uses that directory and
    nothing else is set here. Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`. Call it from a script's ``main``, before the
    first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["DEFAULT_CACHE_DIR", "mesh", "ensure_host_devices",
           "use_compile_cache"]
