"""Multi-process pod launcher (launch/dist_run.py).

The 2-process spawn costs three full XLA compiles (two workers + the
single-process reference), so the end-to-end check rides the slow lane;
CI runs the same command directly in its own smoke job.  The pure
helpers stay tier-1.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.launch.dist_run import _losses, _mesh_size, build_argparser


def test_mesh_size_and_default_spec():
    assert _mesh_size("pod:2") == 2
    assert _mesh_size("pod:2,data:2,model:2") == 8
    args = build_argparser().parse_args(["--nproc", "4"])
    from repro.launch.dist_run import _mesh_spec
    assert _mesh_spec(args) == "pod:4"


def test_losses_parser_filters_tagged_lines():
    out = "\n".join([
        '{"mesh": {"pod": 2}}',
        'DISTLOSS {"step": 1, "loss_hex": "0x1.8p+2", "loss": 6.0}',
        "noise",
        'DISTLOSS {"step": 2, "loss_hex": "0x1.9p+2", "loss": 6.25}',
    ])
    recs = _losses(out)
    assert [r["step"] for r in recs] == [1, 2]
    assert float.fromhex(recs[0]["loss_hex"]) == 6.0


@pytest.mark.parametrize("platform,refused", [
    ("", False), ("cpu", False), ("tpu", True)])
def test_pod_refuses_a_non_cpu_platform(monkeypatch, platform, refused):
    """The pod's workers run on the host CPU; asked for another platform
    it refuses and points at the one-process mesh path."""
    from repro.launch.dist_run import _check_platform
    monkeypatch.setenv("JAX_PLATFORMS", platform)
    if refused:
        with pytest.raises(SystemExit, match="--mesh replica:4"):
            _check_platform()
    else:
        _check_platform()


@pytest.mark.slow
def test_two_process_run_matches_single_process_bitwise():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.dist_run", "--nproc", "2",
         "--mesh", "pod:2", "--algo", "parle", "--smoke",
         "--steps", "6", "--L", "3", "--port", "9321"],
        env=env, capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stdout + res.stderr
    verdict = json.loads(res.stdout.strip().splitlines()[-1])
    assert verdict["bitwise_equal"] is True, verdict
    assert verdict["compared_steps"] == 6
