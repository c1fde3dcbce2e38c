"""``chip_smoke.py`` refuses a host with no TPU, and its phases, run at a
tiny size on the CPU devices, still drive the system's entry points."""

import importlib.util
import json
from pathlib import Path

import jax
import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_to_run_without_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert "no TPU" in out.err
    for line in out.out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_one_chip_phases_tiny(smoke, capsys):
    smoke.run_one_chip(jax.devices(), cols=1024, sweeps=2,
                       msg_elems=1 << 12, require_compiled_kernel=False)
    out = capsys.readouterr().out
    assert "host rung bit-exact" in out
    assert "agrees with kernels/jacobi/ref.py" in out


def test_four_chip_phases_tiny(smoke, capsys):
    # 4 MiB messages: above the multipath threshold, so the diagonal
    # pair plans several paths as it does at 512 MiB.
    smoke.run_four_chips(jax.devices(), msg_elems=1 << 20, cols=256,
                         iters=2)
    out = capsys.readouterr().out
    assert "diagonal pair planned multipath" in out
    assert "exchange 3->0 bit-exact" in out
    assert "captured Jacobi bitwise equal" in out
