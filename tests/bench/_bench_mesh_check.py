"""A tiny cell through the harness on a mesh of 4 CPU devices: a sound run
compares exactly with the reference, and a run with the exchange between
the chips left out (``psum`` returning the local part) does not.

Run as its own process: the host device count is fixed before JAX starts.
"""
import json
import os
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src"), HERE]

import jax  # noqa: E402

import run  # noqa: E402
import spec  # noqa: E402
from conftest import TINY, TINY_MIX  # noqa: E402


def main() -> None:
    assert len(jax.devices()) == 4
    tmp = tempfile.mkdtemp()
    mix = os.path.join(tmp, "mix.json")
    with open(mix, "w") as f:
        json.dump(TINY_MIX, f)
    sizes = {**TINY, "n_warehouses": 3}
    spec.config = lambda name: sizes
    spec.traffic_path = lambda name: mix
    spec.cell = lambda name: spec.Cell(name, "tiny", "mix", 4)
    r = run.run("tiny.mix", 2**31 + 7, 60.0, False, allow_cpu=True)
    assert r["correct"] and r["device"]["count"] == 4, r
    print("sound", r["checks"])
    jax.lax.psum = lambda x, axis_name, **kw: x
    r = run.run("tiny.mix", 2**31 + 7, 60.0, False, allow_cpu=True)
    assert not r["correct"], r
    print("no exchange", r["checks"])
    print("MESH_OK")


if __name__ == "__main__":
    main()
