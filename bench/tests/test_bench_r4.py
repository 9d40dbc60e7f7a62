"""The four-replica training cell (``bench/cells/train.stablelm2.r4.json``,
not in BENCHMARK.json until the program can initialise it on four chips)
on four virtual CPU devices at a tiny size: the checked steps and the outer
step's collective-permute with each replica's own random partner agree with
the reference; with the exchange left out they do not."""

import json
import os
import subprocess
import sys

import pytest

from bench.tests.helpers import ROOT

SCRIPT = r"""
import json, sys
sys.path[:0] = ['src', '.']
import jax
from bench.core import harness as H
from bench.drivers import train as D
from bench.tests.helpers import SEED, TINY_TRAIN

cell = H.load('cells', 'train.stablelm2.r4')
cfg = H.load('configs', cell['config'])
cfg['model'].update(TINY_TRAIN['model'])
tr = dict(H.load('traffic', cell['traffic']), **TINY_TRAIN['traffic'])
devs = jax.devices()[:4]

def readings():
    su = D.Setup(cell, cfg, tr, SEED, devs)
    prog = D.setup_and_check_steps(su)
    words, batches, dims = su.words, su.batches, su.dims
    del su
    return D.compare(prog, D.reference(cell, dims, words, batches, 4, tr['per_replica_batch'], SEED, devs))

sound = readings()
from repro.parallel import steps as ST
ST.OuterProgramPool.pairs_for = lambda self, i, *a, **k: (0, [(r, r) for r in range(self.plan.replicas)])
alone = readings()
num = lambda c: {k: v for k, v in c.items() if isinstance(v, float)}
print(json.dumps({'sound': num(sound), 'alone': num(alone)}))
"""


@pytest.mark.multidevice
def test_train_r4_exchange_is_checked_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    # float32 at this size: sound runs agree to rounding
    assert max(out["sound"].values()) < 1e-4, out["sound"]
    assert out["alone"]["outer_gap"] > 1e-2, out["alone"]
