"""chip_smoke.py's contract off the chip: it refuses, fast, and its parent
never touches jax (a parent that has holds the chip its children need)."""

import ast
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t = time.monotonic()
    p = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=120)
    return p, time.monotonic() - t


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in_checkout", "script_alone"])
def test_refuses_without_a_tpu(tmp_path, alone):
    """On a CPU — in the checkout, or in a directory holding nothing but
    the script — no phase runs: exit code != 0 and ``"ok": false`` on the
    last stdout line, within seconds."""
    script, cwd = _SMOKE, _REPO
    if alone:
        script = shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
        cwd = tmp_path
    p, secs = _run(str(script), str(cwd))
    assert p.returncode != 0
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"].get("platform") != "tpu"
    assert "phase=train" not in p.stdout and "phase=data" not in p.stdout
    assert secs < 60


def test_parent_is_stdlib_only():
    """Every import in chip_smoke.py, at any depth, is standard library:
    jax, numpy and tpuic appear only inside the child scripts it spawns
    (string constants)."""
    mods = set()
    for node in ast.walk(ast.parse(open(_SMOKE).read())):
        if isinstance(node, ast.Import):
            mods |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mods.add((node.module or "").split(".")[0])
    assert mods <= set(sys.stdlib_module_names), mods - set(
        sys.stdlib_module_names)
