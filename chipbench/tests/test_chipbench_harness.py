"""The benchmark harness on the CPU: pieces found by name, the counts and
peaks, and both drivers end to end at smoke widths."""
import argparse
import dataclasses
import json
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from chipbench import catalog, counts, run

ROOT = Path(__file__).resolve().parents[1]
CHECKOUT = ROOT.parent

QWEN_SMOKE = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                  head_dim=16, d_ff=96, vocab_size=300)
RWKV_SMOKE = dict(num_layers=2, d_model=64, d_ff=128, vocab_size=256,
                  rwkv_head_dim=16)


def smoke_cell(name, model, **traffic):
    cell = catalog.load_cell(name)
    return dataclasses.replace(
        cell, config=dict(cell.config, model=dict(cell.config["model"], **model)),
        traffic=dict(cell.traffic, **traffic))


def serve_smoke():
    return smoke_cell("qwen1.5-4b.decode-b16", QWEN_SMOKE, batch=3, prompt_len=8,
                      new_tokens=6, batch_seconds=1.0, check_sequences=2)


def train_smoke():
    return smoke_cell("rwkv6-1.6b.train-fsdp4", RWKV_SMOKE, batch=4, seq_len=64,
                      step_seconds=1.0)


def execute(cell, monkeypatch, seed=2**31 + 99):
    """A whole run after the look for the chip, on the CPU."""
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    args = argparse.Namespace(seed=seed, seconds=2.0, trace=0)
    return run.execute(cell, args, jax.devices())


# ---------------------------------------------------------------------------
# found by name

def test_every_cell_of_the_benchmark_has_its_files():
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    assert {w["name"] for w in bench["workloads"]} <= set(catalog.workload_names())
    for w in bench["workloads"]:
        cell = catalog.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.config["name"] == w["config"]
        catalog.load_driver(cell.driver)
    for c in bench["configs"]:
        assert json.loads((CHECKOUT / c["file"]).read_text())["source"] == c["source"]


def test_per_layer_metrics_are_read_in_the_cells_that_list_them():
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    kinds = {w["name"]: catalog.load_cell(w["name"]).driver for w in bench["workloads"]}
    for metric in bench["per_layer"]:
        for cell in metric["workloads"]:
            readers = catalog.layer_metric_readers(kinds[cell])
            assert metric["name"] in readers
            assert readers[metric["name"]].UNIT == metric["unit"]


def test_a_new_workload_file_is_found_without_code(tmp_path):
    root = tmp_path / "chipbench"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns("__pycache__"))
    wl = json.loads((root / "workloads" / "qwen1.5-4b.decode-b16.json").read_text())
    wl.update(name="qwen1.5-4b.decode-b4", why="a smaller batch")
    wl["traffic"]["batch"] = 4
    (root / "workloads" / "qwen1.5-4b.decode-b4.json").write_text(json.dumps(wl))
    (root / "layer_metrics" / "tokens.decode.py").write_text(
        'KIND = "serve"\nUNIT = "tokens"\n\n\ndef read(ctx):\n'
        '    return ctx["counts"].get("output_tokens")\n')
    assert "qwen1.5-4b.decode-b4" in catalog.workload_names(root)
    cell = catalog.load_cell("qwen1.5-4b.decode-b4", root)
    assert cell.traffic["batch"] == 4 and cell.config["name"] == "qwen1.5-4b"
    assert "tokens.decode" in catalog.layer_metric_readers("serve", root)
    with pytest.raises(FileNotFoundError):
        catalog.load_cell("no-such-cell", root)


# ---------------------------------------------------------------------------
# counts and peaks

def test_full_width_counts():
    qwen = catalog.load_cell("qwen1.5-4b.decode-b16").config["model"]
    rwkv = catalog.load_cell("rwkv6-1.6b.train-fsdp4").config["model"]
    # 40 x (4 x 2560^2 + 3 x 2560 bias + 3 x 2560 x 6912 + 2 x 2560)
    # + 2560 + 2560 x 151936 = 3,561,413,120 parameters past the embedding
    assert counts.dense_decode_flops_per_token(qwen) == 2 * 3_561_413_120
    assert counts.kv_bytes_per_token(qwen) == 409_600
    # 24 x 55,470,080 + 2 x 2048 + 2048 x 65536
    n = 1_465_503_744
    assert counts.rwkv6_nonembedding_params(rwkv) == n
    assert counts.rwkv6_train_flops_per_token(rwkv) == pytest.approx(6 * n, rel=0.005)
    assert counts.decode_step_bytes(qwen, 16, 320) == pytest.approx(
        2 * 3_561_413_120 + 16 * 2560 * 2 + 16 * 320 * 409_600)


def test_counts_match_the_programs_parameter_tree():
    import numpy as np
    from repro import config as C
    from repro.models import build_model
    cell = catalog.load_cell("rwkv6-1.6b.train-fsdp4")
    m = cell.config["model"]
    tree = build_model(C.get(cell.config["arch"]).full.replace(**m)).abstract()
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert n - int(np.prod(tree["embed"].shape)) == counts.rwkv6_nonembedding_params(m)


def test_peaks_by_device_kind():
    v5e = catalog.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        catalog.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# runs

def test_run_refuses_to_start_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "run.py"), "--workload", "qwen1.5-4b.decode-b16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_serve_driver_end_to_end(monkeypatch):
    line = execute(serve_smoke(), monkeypatch)
    assert line["correct"] is True
    assert line["attempted"] == 6 and line["failed"] == 0
    assert set(line["metrics"]) == {"decode_tokens_per_s", "itl_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"bad_batches", "max_gap", "mean_gap"}
    json.dumps(line)


def test_train_driver_end_to_end(monkeypatch):
    line = execute(train_smoke(), monkeypatch)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] == 2 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["checks"]) == {"rows_not_as_seeded", "loss_gap", "grad_gap",
                                   "change_gap", "window_nonfinite_losses"}
    json.dumps(line)
