"""The four-chip training cell on four virtual CPU devices: a sound run is
correct, and one whose chips leave out the gradient exchange (each keeps
its own shard's gradient) is not. Runs in a child process, which alone can
ask for four host devices."""
import json
import os
import subprocess
import sys
from pathlib import Path

CHILD = r'''
import json, sys
import tinycell
import jax
from jax.sharding import PartitionSpec as P
from repro.train.engine import TrainEngine

fault = sys.argv[1] == "no_exchange"
if fault:
    def one_step(self, params, opt_state, batch):
        # Each chip's gradient of its own rows, never summed across chips.
        local = jax.shard_map(jax.value_and_grad(self.loss_fn), mesh=self.mesh,
                              in_specs=(P(), P("data")), out_specs=(P(), P()),
                              check_vma=False)
        loss, grads = local(params, batch)
        params, opt_state = self._apply_update(params, opt_state, grads, batch)
        return params, opt_state, {"loss": loss}
    TrainEngine._one_step = one_step
cell = tinycell.tiny_cell("dbn-baidu.train-dp4", sys.argv[2], chips=4)
record = tinycell.run(cell)
print(json.dumps({c.name: [c.value, c.ok] for c in record.checks}))
'''


def _child(mode, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    here = Path(__file__).resolve().parent
    out = subprocess.run([sys.executable, "-c", CHILD, mode, str(tmp_path)],
                         cwd=here, env=dict(env, PYTHONPATH=str(here)),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(tmp_path):
    checks = _child("sound", tmp_path)
    assert all(ok for _, ok in checks.values()), checks


def test_exchange_left_out_is_caught(tmp_path):
    checks = _child("no_exchange", tmp_path)
    assert not all(ok for _, ok in checks.values()), checks
