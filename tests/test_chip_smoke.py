"""chip_smoke.py off the chip: it refuses to run, and its phases hold.

The smoke proves the main path on a TPU; here it must fail before it prints
a result. Its phases are then run in-process at a tiny width on the CPU,
with the device-only checks pointed at what the CPU leaves instead, so a
change that breaks the smoke's control flow shows up without a chip.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def _run_script(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)  # the script finds src/ itself
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _printed_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_smoke_fails_without_a_tpu():
    proc = _run_script(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not _printed_result(proc.stdout)


def test_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run_script(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no repro package" in proc.stderr
    assert not _printed_result(proc.stdout)


def test_smoke_refuses_rerouted_kernels():
    proc = _run_script(ROOT, {"JAX_PLATFORMS": "cpu",
                              "CLAX_KERNEL_IMPL_SESSION_NLL": "ref"})
    assert proc.returncode != 0
    assert "CLAX_KERNEL_IMPL_SESSION_NLL" in proc.stderr


@pytest.fixture()
def smoke(monkeypatch, tmp_path):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke
    from repro.core import Compression, EmbeddingParameterConfig
    from repro.core import MODEL_REGISTRY
    from repro.launch import compile_cache

    def tiny_model(kind):
        # clax_baidu's 2^31 hashed ids, hashed into ~1k rows
        attraction = EmbeddingParameterConfig(
            parameters=1 << 31, compression=Compression.HASH,
            compression_ratio=float(1 << 21), baseline_correction=True,
            init_logit=-2.0)
        return MODEL_REGISTRY[kind](positions=10, attraction=attraction,
                                    satisfaction=attraction)

    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "device_check", lambda chips: cpu)
    monkeypatch.setattr(chip_smoke, "model", tiny_model)
    monkeypatch.setattr(chip_smoke, "KERNEL_MARK", "HloModule")
    monkeypatch.setattr(chip_smoke, "DEVICE_PLANE", "/host:CPU")
    monkeypatch.setattr(chip_smoke, "peak_bytes", lambda: 0)
    monkeypatch.setattr(chip_smoke, "BAG_SHAPE", (40, 3, 300, 16))
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)
    return chip_smoke


def test_smoke_phases_pass_at_tiny_width(smoke, capsys):
    from repro.kernels import override_impl

    with override_impl(examination_nll="pallas", session_nll="pallas"):
        device = smoke.run(chips=1, batch=64)
    assert device["platform"] == "cpu"
    out = capsys.readouterr().out
    for line in ("embedding_bag float32 40x3 bags over 300x16",
                 "embedding_bag bfloat16 40x3 bags over 300x16",
                 "examination_nll 64x10",
                 "dbn: compiled chunk runs examination_nll",
                 "dctr: compiled chunk runs session_nll",
                 "ubm: first-batch loss",
                 "sessions/s (smoke figure, not a benchmark)",
                 "serve: click probabilities vs ref"):
        assert line in out
    json.dumps(device)


def _off_by_1e4(fn):
    return lambda *a, **kw: fn(*a, **kw) * (1.0 + 1e-4)


@pytest.mark.parametrize("module,name,phase", [
    ("embedding_bag", "embedding_bag_pallas", "check_embedding_bag"),
    ("examination_nll", "_click_nll", "check_examination_nll_near_one"),
])
def test_smoke_kernel_checks_catch_a_kernel_off_its_ref(smoke, monkeypatch,
                                                        module, name, phase):
    import importlib

    mod = importlib.import_module(f"repro.kernels.{module}")
    monkeypatch.setattr(mod, name, _off_by_1e4(getattr(mod, name)))
    if module == "embedding_bag":
        from repro.kernels import ops
        monkeypatch.setattr(ops, name, getattr(mod, name))
    args = (64,) if phase.endswith("near_one") else ()
    with pytest.raises(SystemExit):
        getattr(smoke, phase)(*args)
