"""A run needs a TPU whose kind is in bench/peaks.json."""

import bench_path  # noqa: F401
import pytest

import run
import spec


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99 imaginary")
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_non_tpu_backend_refused(capsys):
    rc = run.main(["--workload", "qwen2-0.5b.train-2k", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err


def test_too_few_chips_refused(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    assert run.find_devices(1)["kind"] == "TPU v5 lite"
    with pytest.raises(run.NoAccelerator):
        run.find_devices(4)


def test_checkout_without_program_fails(tmp_path, capsys):
    import shutil
    import subprocess
    import sys

    shutil.copytree(bench_path.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(bench_path.ROOT / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.train-2k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode != 0 and p.stdout == ""
