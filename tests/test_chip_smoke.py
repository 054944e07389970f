"""chip_smoke.py on the CPU: its phases at the smoke preset, and its refusal
to report a result without a TPU.

The phases are the same functions the chip runs at full width; here they
run the tiny same-shape ``qwen2_100m`` preset with the Pallas kernels in
interpret mode (routing floor lowered to 1 so every leaf goes through
them).  ``main()`` must exit non-zero on the CPU, and print no result line,
both from the repository and from a directory that holds only the script.
"""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SMOKE = dict(preset="smoke", seq=32, batch_per_device=2)


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("aggregate", ["dense_masked", "sparse_gather"])
def test_qwen_rounds_phase(chip_smoke, aggregate):
    res = chip_smoke.phase_qwen_rounds(aggregate, "pallas", rounds=3,
                                       pallas_min_elems=1, **SMOKE)
    assert len(res["losses"]) == 3
    assert res["compile_and_first_round_s"] > 0 < res["steady_round_s"]
    # interpret mode on the CPU: no Mosaic kernel in the HLO
    assert res["tpu_custom_call"] is False


def test_pallas_vs_exact_phase(chip_smoke):
    res = chip_smoke.phase_pallas_vs_exact(pallas_min_elems=1, **SMOKE)
    assert res["bitwise"] and res["max_abs_diff"] == 0.0


def test_cnn_window_phase(chip_smoke):
    res = chip_smoke.phase_cnn_window(n_train=400)
    assert res["sync_windows"] == 2 and res["uplink_mb"] > 0


def test_four_chip_phase_on_a_host_mesh(tmp_path):
    """The --four-chips phase on four virtual CPU devices (a fresh process:
    the host device count is fixed before the backend starts)."""
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(REPO / 'src')!r})
        from repro.launch.compat import force_host_device_count
        force_host_device_count(4)
        sys.path.insert(0, {str(REPO)!r})
        import chip_smoke
        res = chip_smoke.phase_four_chips(preset="smoke", seq=32,
                                          batch_per_device=2)
        print(json.dumps(res))
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ef_rows_distinct"]
    assert res["saturating sparse_gather"]["losses"] == pytest.approx(
        res["saturating dense_masked"]["losses"], abs=1e-4)


def test_main_refuses_the_cpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True,
                         env={**env, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
