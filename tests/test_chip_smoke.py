"""`chip_smoke.py`'s phases at tiny sizes on the CPU (kernels interpreted):
the script's control flow, paths and checks, without the chip.  The real
run needs a TPU and is refused here (last test)."""
import dataclasses
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _tiny_wcfg(h=2):
    from repro.configs import sagips_gan
    base = sagips_gan.PAPER
    return dataclasses.replace(
        base, n_param_samples=8, events_per_sample=4,
        sync=dataclasses.replace(base.sync, h=h))


@pytest.fixture(scope="module")
def watch():
    return chip_smoke.CompileWatch()


def test_sampler_phase_interpreted_matches_oracle(watch):
    with chip_smoke.phase("sampler", watch):
        chip_smoke.phase_sampler(2, 16, 8)


def test_trainer_chip_vs_cpu_and_serving_phases(watch):
    from repro.configs import serving as serving_cfg
    wcfg = _tiny_wcfg()
    state = None
    for impl in ("jnp", "pallas"):
        state = chip_smoke.phase_trainer(wcfg, 2, 2, 4, 2, 400, impl)
    assert int(state["epoch"][0]) == 4
    chip_smoke.phase_chip_vs_cpu(wcfg, 400)
    chip_smoke.phase_serving(state, wcfg.problem, serving_cfg.REDUCED,
                             (10, 16, 40, 64), watch)


def test_compare_weights_rejects_a_wrong_update():
    import numpy as np
    lr = 1e-5
    w0 = {"w": np.zeros(10_000, np.float32)}
    step = np.where(np.arange(10_000) % 2 == 0, lr, -lr).astype(np.float32)
    flipped = step.copy()
    flipped[:5] *= -1                       # a few sign flips: allowed
    chip_smoke.compare_weights({"w": w0["w"] + step},
                               {"w": w0["w"] + flipped}, lr, 1, "flips")
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_weights({"w": w0["w"] + step},
                                   {"w": w0["w"] - step}, lr, 1, "wrong")


_FOUR = r"""
import os, sys, json, dataclasses
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "src"); sys.path.insert(0, ".")
import chip_smoke
from repro.configs import sagips_gan
base = sagips_gan.PAPER
wcfg = dataclasses.replace(base, n_param_samples=8, events_per_sample=4,
                           sync=dataclasses.replace(base.sync, h=2))
print("RESULT " + json.dumps(chip_smoke.phase_four_chips(wcfg, 400, 3)))
"""


@pytest.mark.slow
def test_four_chip_phase_on_four_host_devices():
    res = subprocess.run([sys.executable, "-c", _FOUR], cwd=REPO,
                         capture_output=True, text=True, timeout=600)
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, f"child failed:\n{res.stdout[-2000:]}\n{res.stderr[-3000:]}"
    assert json.loads(line[0][len("RESULT "):]) == [0, 1, 2, 3]


def test_script_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["error"]
