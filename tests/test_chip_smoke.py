"""chip_smoke.py's phases at smoke widths on the CPU, with the Pallas kernels
interpreted, and the script's refusal to run anywhere but on a TPU."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import config as C
from repro.core import V5E

REPO = Path(__file__).resolve().parents[1]

FOUR_DEVICES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
from pathlib import Path
sys.path.insert(0, os.environ["CHIP_SMOKE_DIR"])
import chip_smoke
from repro import config as C
peaks, state_bytes = chip_smoke.phase_four_chips(
    C.get("rwkv6-1.6b").smoke, batch=8, seq=64, steps=2, cut_layers=1,
    out_dir=Path(os.environ["CHIP_SMOKE_OUT"]))
assert len(peaks) == 4 and state_bytes > 0
print("four_chips_ok")
"""


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_decode_agrees_with_forward(smoke):
    res = smoke.phase_serve(C.get("qwen1.5-4b").smoke, batch=2, prompt_len=16,
                            max_new=8)
    assert res["f32_argmax_agreement"] >= smoke.MIN_AGREEMENT
    assert (res["bf16_decode_vs_f32"]
            >= res["bf16_forward_vs_f32"] - smoke.MAX_BF16_SHORTFALL)
    assert res["prefill_s"] > 0 and res["decode_tok_per_s"] > 0


def test_train_and_simulate_phases(smoke, tmp_path):
    rc, step_s = smoke.phase_train(C.get("lenet").smoke, steps=12,
                                   ckpt_dir=tmp_path / "ckpt")
    assert rc.mesh.num_devices == 1 and step_s > 0
    assert (tmp_path / "ckpt").is_dir()
    assert smoke.phase_simulate(rc, V5E, step_s) > 0


def test_kernel_phase_matches_oracles(smoke):
    cases = smoke.kernel_cases(C.get("qwen1.5-4b").smoke, seq=128,
                               conv_shape=(1, 8, 8, 4))
    res = smoke.phase_kernels(cases)
    assert set(res) == {"flash_attention", "tiled_matmul", "winograd"}
    # interpreted here: no TPU kernel in a CPU executable
    assert not any(r["compiled"] for r in res.values())


def test_spread_check(smoke):
    smoke.check_spread([9, 3, 3, 3], 10)
    with pytest.raises(AssertionError):
        smoke.check_spread([10, 3, 3, 3], 10)
    with pytest.raises(AssertionError):
        smoke.check_spread([None] * 4, 10)


def test_four_chip_phase_on_virtual_devices(tmp_path):
    res = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES], capture_output=True, text=True,
        timeout=600, env={**os.environ, "PYTHONPATH": str(REPO / "src"),
                          "CHIP_SMOKE_DIR": str(REPO),
                          "CHIP_SMOKE_OUT": str(tmp_path)})
    assert res.returncode == 0, res.stderr[-3000:]
    assert "four_chips_ok" in res.stdout


def test_compile_cache_directory(monkeypatch, tmp_path):
    import jax
    from repro.runtime.compile_cache import REPO_CACHE_DIR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_refuses_to_run_without_a_tpu():
    res = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], capture_output=True,
        text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr
    assert "[serve]" not in res.stdout and "[train]" not in res.stdout
