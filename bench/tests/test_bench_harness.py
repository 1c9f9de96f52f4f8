"""The harness: least work from the configuration, files found by name,
the open-loop schedule fixed by the seed, the control (the reference in
bfloat16 in the program's place) failing the limits, and ``run.py``
refusing to measure without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import tinycell
from harness import cell as cell_mod
from harness.cell import find_cell, load_json

BENCH = tinycell.BENCH
ROOT = BENCH.parent
serve = cell_mod.load_module(BENCH / "drivers" / "serve.py")


@pytest.mark.parametrize("config,table_gb", [("dbn-baidu", 10.31),
                                             ("dctr-baidu", 5.15)])
def test_least_work_is_the_dense_adamw_bound(config, table_gb):
    cfg = load_json(BENCH / "configs" / f"{config}.json")
    work = cell_mod.load_module(BENCH / "work" / f"{config}.py")
    flops, nbytes = work.least_work(cfg)
    tables = len(cfg["tables"])
    assert nbytes == pytest.approx(table_gb * 1e9, rel=5e-3)
    assert nbytes >= tables * 24 * 214_748_672
    # memory-bound: the bytes' time dwarfs the FLOPs'
    assert nbytes / 819e9 > 100 * flops / 197e12


def test_cells_find_their_files_by_name(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "out", "tests", "__pycache__"))
    spec = load_json(ROOT / "BENCHMARK.json")
    # A new configuration, traffic mix, cell and per-layer metric: new files
    # and entries only.
    cfg = load_json(bench / "configs" / "dbn-baidu.json")
    cfg["name"] = "dbn-new"
    (bench / "configs" / "dbn-new.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "configs" / "dbn-baidu.py", bench / "configs" / "dbn-new.py")
    shutil.copy(bench / "work" / "dbn-baidu.py", bench / "work" / "dbn-new.py")
    mix = load_json(bench / "traffic" / "train.json")
    mix["batches"] = 16
    (bench / "traffic" / "train-new.json").write_text(json.dumps(mix))
    (bench / "limits" / "dbn-new.train-new.json").write_text('{"loss": 1}')
    (bench / "metrics" / "train.new_metric.py").write_text(
        "def reduce(run):\n    return 42.0\n")
    spec["configs"].append(dict(spec["configs"][0], name="dbn-new",
                                file="bench/configs/dbn-new.json"))
    spec["workloads"].append({"name": "dbn-new.train-new", "config": "dbn-new",
                              "traffic": "train-new", "chips": 1, "why": "x"})
    for metric in spec["end_to_end"] + spec["per_layer"]:
        if "dbn-baidu.train" in metric.get("workloads", ()):
            metric["workloads"].append("dbn-new.train-new")
    spec["per_layer"].append({"name": "train.new_metric", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "data", "moves": "train_sessions_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = find_cell("dbn-new.train-new", bench=bench)
    assert cell.config["name"] == "dbn-new"
    assert cell.traffic["batches"] == 16
    assert cell.limits == {"loss": 1}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "train_sessions_per_s"]
    names = [m["name"] for m in cell.per_layer]
    assert "train.new_metric" in names          # no workloads key: moves
    assert "train.step_mfu" in names
    assert "train.collective_ms_per_step" not in names
    assert cell.metric("train.new_metric").reduce(None) == 42.0
    assert hasattr(cell.driver(), "Replay")
    assert cell.reference().LEAVES[0] == "attraction/table"
    assert cell.work().least_work(cell.config)[1] > 1e10


def test_open_loop_schedule_is_fixed_by_the_seed():
    schedule = serve.schedule
    a = schedule(2 ** 40 + 7, 2000.0, 5000)
    b = schedule(2 ** 40 + 7, 2000.0, 5000)
    c = schedule(2 ** 40 + 8, 2000.0, 5000)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0)
    # the same gaps in another order: the same work under every seed
    gaps = [np.sort(np.diff(x, prepend=0.0)) for x in (a, c)]
    assert np.allclose(gaps[0], gaps[1], rtol=0, atol=1e-12)
    assert a[-1] == pytest.approx(c[-1]) and a[-1] == pytest.approx(2.5, rel=0.01)


@pytest.mark.parametrize("name", ["dbn-baidu.train", "dctr-baidu.train"])
def test_control_in_bfloat16_fails_the_training_limits(name, tmp_path):
    import jax.numpy as jnp

    from harness.compare import counted_leaves, judge, leaf_gap, max_rel_gap
    from harness.follow import follow
    from harness.sessions import SessionSource

    cell = tinycell.tiny_cell(name, tmp_path)
    ref = cell.reference()
    source = SessionSource(cell.traffic["sessions"], cell.seed)
    batches = [source.chunk(i, cell.config["batch"]) for i in range(2)]
    want = follow(ref, cell.config, batches)
    control = follow(ref, cell.config, batches, dtype=jnp.bfloat16)
    values = {"loss": max_rel_gap(control["losses"], want["losses"]),
              "grad": leaf_gap(control["mu"], want["mu"]),
              "step": leaf_gap(control["step"], want["step"],
                               counted_leaves(want["grad1"]))}
    assert not all(c.ok for c in judge(values, cell.limits)), values


def test_control_in_bfloat16_fails_the_serving_limit(tmp_path):
    import jax
    import jax.numpy as jnp

    from harness.sessions import SessionSource

    cell = tinycell.tiny_cell("dbn-baidu.serve-poisson", tmp_path)
    ref = cell.reference()
    params = serve.draw_params(ref, cell.config, 5)
    rows = SessionSource(cell.traffic["sessions"], cell.seed).chunk(0, 256)
    want = np.exp(np.asarray(ref.click_log_probs(params, rows, cell.config),
                             np.float64))
    low = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    got = np.exp(np.asarray(ref.click_log_probs(low, rows, cell.config)
                            .astype(jnp.float32), np.float64))
    gap = float(np.max(np.abs(got - want) / want))
    assert gap > cell.limits["click_prob"], gap


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           "dbn-baidu.train", "--seed", str(2 ** 33 + 1),
                           "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_refuses_without_a_tpu():
    out = _run_py(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_refuses_a_rerouted_kernel():
    out = _run_py(ROOT, {"CLAX_KERNEL_IMPL": "ref"})
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns(
        "out", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run_py(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
