"""``chip_smoke.py``'s contract, as far as a CPU sandbox can check it.

* without the rehearsal flag it exits non-zero when jax's platform is
  not ``tpu`` and prints no result line;
* alone in a directory — without the program — it fails too;
* ``--rehearse-cpu`` runs every leg end to end at toy widths on the
  virtual CPU mesh, says it is a rehearsal, and ends with the JSON
  result line.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, pythonpath=REPO, timeout=840):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = pythonpath
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_refuses_to_run_off_the_chip():
    r = _run([SMOKE])
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "no TPU" in r.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py", "--rehearse-cpu"], cwd=str(tmp_path),
             pythonpath="")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "zoo_tpu" in r.stderr


def test_unknown_leg_is_an_error():
    r = _run([SMOKE, "--rehearse-cpu", "--legs", "train,nonsense"])
    assert r.returncode != 0 and '"ok"' not in r.stdout


def test_rehearsal_runs_every_leg_on_the_cpu_mesh():
    r = _run([SMOKE, "--rehearse-cpu"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert lines[0] == "platform=cpu device_kind=cpu device_count=8"
    assert "compile_cache=" in lines[1] and "jax=" in lines[1]
    assert any(line.startswith("REHEARSAL") for line in lines)
    for leg in ("train", "serve", "kernels", "multichip"):
        assert any(line.startswith(f"leg {leg}: ok") for line in lines), \
            (leg, r.stdout[-3000:])
    result = json.loads(lines[-1])
    assert result == {"ok": True, "rehearsal": True,
                      "device": {"platform": "cpu", "kind": "cpu",
                                 "count": 8}}
