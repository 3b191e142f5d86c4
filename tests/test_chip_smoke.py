"""chip_smoke.py off the chip: its phases run at `tiny` on the CPU (Pallas
in interpret mode), only `main` demands a TPU, and a copy of the script
without the repository refuses to run."""
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.mark.parametrize("mode", chip_smoke.TRAIN_MODES)
def test_train_phase_at_tiny(mode):
    report = chip_smoke.train_phase(mode, chip_smoke.TINY)
    assert len(report.metrics) == 3
    assert report.compile_s > 0 and len(report.step_s) == 3


def test_parity_phase_at_tiny(capsys):
    chip_smoke.parity_phase(chip_smoke.TINY)
    assert "parity ghost_flat pallas vs xla" in capsys.readouterr().out


def test_serve_phase_at_tiny(capsys):
    chip_smoke.serve_phase(chip_smoke.TINY)
    out = capsys.readouterr().out
    assert "serve paged engine" in out and "paged_attn pallas vs xla" in out


def test_sharded_phase_at_tiny_on_four_cpu_devices():
    """The --chips 4 path on four virtual CPU devices: the mesh run must
    match the one-device run and spread every parameter over 4 devices."""
    code = ("import chip_smoke\n"
            "chip_smoke.sharded_phase(chip_smoke.TINY)\n"
            "print('SHARDED_OK')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
    assert "model-axis norm collectives 0" in out.stdout  # per_group


def test_main_refuses_to_run_off_the_chip(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


def test_script_alone_refuses_to_run(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_missing_kernels_reads_the_compiled_program():
    hlo = ('  %ghost_norm.1 = f32[4,8,128] custom-call(%a, %g), '
           'custom_call_target="tpu_custom_call", metadata={op_name='
           '"jit(step_fn)/transpose/ghost_norm/pallas_call"}\n')
    choices = {("norms", 512, 2560, 6144): "pallas",
               ("scale_contract", 512, 2560, 6144): "pallas",
               ("clip_sum", 16, 64, 64): "xla"}
    assert chip_smoke.kernels_in(hlo) == {"ghost_norm"}
    assert chip_smoke.missing_kernels(choices, hlo) == ["scale_contract"]
    assert chip_smoke.missing_kernels({}, "") == []
