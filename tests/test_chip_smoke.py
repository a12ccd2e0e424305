"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
one-chip and four-replica phases pass their own checks at smoke width
on the CPU (the chip run adds the kernel check and the full width)."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

from repro.configs import get_smoke_config

REPO = Path(__file__).resolve().parents[1]
# 77 is not a page multiple (the twin copies its boundary page), and the
# prompts past one 256-token chunk take the chunked-prefill path
SMOKE_SIZES = {"PROMPT_LENS": (77, 30, 64, 130, 200, 260), "NUM_PAGES": 64,
               "MAX_LEN": 320, "NEW_TOKENS": 8,
               "FOUR_CHIP_PROMPT_LENS": (40, 64, 100, 128, 50, 60, 110, 120)}


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_without_tpu_exits_nonzero_and_prints_no_result():
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_one_chip_phase_passes_its_checks_at_smoke_width(monkeypatch):
    cs = _chip_smoke()
    for name, value in SMOKE_SIZES.items():
        monkeypatch.setattr(cs, name, value)
    report = cs.one_chip(get_smoke_config("olmo-1b"))
    assert len(report["outs"]) == len(cs.PROMPT_LENS) + 1
    cs.check_one_chip(report)


FOUR_DEVICES = """
import sys
sys.path.insert(0, {repo!r})
import jax
import chip_smoke as cs
from repro.configs import get_smoke_config
for name, value in {sizes!r}.items():
    setattr(cs, name, value)
devices = jax.devices()
report = cs.four_chips(get_smoke_config("olmo-1b"), devices)
cs.check_four_chips(report, devices)
print("replicas", report["replica_devices"])
"""


def test_four_replica_phase_on_four_cpu_devices():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c",
                          FOUR_DEVICES.format(repo=str(REPO),
                                              sizes=SMOKE_SIZES)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "replicas [['TFRT_CPU_0'], ['TFRT_CPU_1']" in out.stdout
