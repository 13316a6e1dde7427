"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
phases pass at a tiny size with Pallas kernels interpreted.

Interpret mode lowers no ``tpu_custom_call``, so the tests blank the
kernel mark the script looks for; everything else is the script's own
code and checks.
"""
import importlib.util
import os
import subprocess
import sys

from conftest import REPO, SRC, TESTS, run_subprocess

SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _shrink(cs):
    cs.KERNEL_MARK = ""
    cs.BATCH, cs.PROMPT_LEN, cs.GEN = 2, 16, 8


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, SCRIPT], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_one_chip_phases_at_tiny_size():
    from repro.launch.mesh import make_host_mesh

    cs = _load_smoke()
    _shrink(cs)
    cs.engine_phase(make_host_mesh(1, 1), n=1024, blocks=8)
    cs.serve_phase(make_host_mesh(1, 1), smoke=True)


def test_four_chip_phases_at_tiny_size():
    code = f"""
import sys, jax
sys.path[:0] = [{SRC!r}, {TESTS!r}]
from test_chip_smoke import _load_smoke, _shrink
cs = _load_smoke()
_shrink(cs)
cs.engine_mesh_phase(jax.devices(), n=1024, blocks=8)
cs.serve_tp4_phase(smoke=True)
print("FOUR OK")
"""
    assert "FOUR OK" in run_subprocess(code, devices=4)
