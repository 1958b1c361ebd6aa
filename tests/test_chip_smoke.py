"""chip_smoke.py's contract, as far as a machine without a chip can hold it:
the dry run passes end to end, and nothing but the explicit switch reaches
it — without a TPU the script fails and prints no result."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, **env):
    # one (real) CPU device: the mesh legs have their own tier-1 coverage
    # (tests/test_parallel.py) and double the dry run's compile time
    base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run([sys.executable, script] + args, cwd=cwd,
                          capture_output=True, text=True, timeout=600,
                          env=dict(base, **env))


def test_dry_run_passes_and_says_it_is_not_a_chip_run():
    out = _run(["--dry-run"])
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "not a chip run" in out.stdout
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "dry_run": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    for leg in ("train", "kernel", "serve", "compact"):
        assert f"== {leg}: ok" in out.stdout
    assert "FAIL" not in out.stdout


def test_without_the_switch_a_cpu_is_a_failure_and_prints_no_result():
    out = _run([])
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout and "== " not in out.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo:
    there is no program to smoke, so the script must not report one."""
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(["--dry-run"], cwd=str(tmp_path), script=str(alone))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
