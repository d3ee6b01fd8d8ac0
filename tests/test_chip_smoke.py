"""``chip_smoke.py`` off the chip: its phases at a small size on the CPU
(interpret mode), its sharded legs on four virtual devices, and its refusal
to report anything without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro.core import CMMEngine, analytic_time_model  # noqa: E402

N, TILE = 256, 128


def _run_smoke(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_paper_legs_and_session_match_eager_on_cpu():
    ph = cs.Phases()
    eng = CMMEngine(timemodel=analytic_time_model())
    errs = cs.paper_legs(ph, eng, N, TILE, np.float32)
    assert set(errs) == {"markov/batched-pallas",
                         "kmeans/batched-pallas", "kmeans/mixed",
                         "synth/batched-pallas"}
    assert max(v for k, v in errs.items() if k != "kmeans/mixed") < 1e-5
    assert 0 < errs["kmeans/mixed"] < cs.MIXED_BOUND
    assert cs.session_leg(eng, N, TILE, np.float32) < 1e-5
    assert all(s >= 0 for s in ph.seconds.values())


def test_check_rejects_bad_results():
    ref = np.ones((4, 4), np.float32)
    with pytest.raises(AssertionError, match="relative error"):
        cs.check("leg", ref * 1.01, ref, 1e-4)
    with pytest.raises(AssertionError, match="shape"):
        cs.check("leg", ref[:2], ref, 1e-4)
    bad = ref.copy()
    bad[0, 0] = np.nan
    with pytest.raises(AssertionError, match="non-finite"):
        cs.check("leg", bad, ref, 1e-4)


def test_sharded_legs_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke as cs
        errs = cs.sharded_legs({N})
        assert set(errs) == {{"summa", "cannon", "reduce-scatter"}}, errs
        assert max(errs.values()) < 1e-5, errs
        print("ok")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "leg sharded/cannon" in p.stdout


def _prints_no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_refuses_cpu():
    p = _run_smoke(ROOT)
    _prints_no_result(p)
    assert "no CPU fallback" in p.stderr


def test_fails_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = _run_smoke(str(tmp_path))
    _prints_no_result(p)
    assert "ModuleNotFoundError" in p.stderr
