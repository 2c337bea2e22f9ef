"""The harness on a mesh of four CPU devices (in a process of its own):
the mesh path compares exactly, and leaving out the exchange between the
chips reads as not correct."""
import os
import subprocess
import sys


def test_mesh_cell_and_its_missing_exchange():
    script = os.path.join(os.path.dirname(__file__), "_bench_mesh_check.py")
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_OK" in out.stdout
