"""Hash-bucket distributed AMI == host AMI, including real multi-shard
routing (subprocess with 8 host devices)."""
import json
import subprocess
import sys

import numpy as np
import jax.numpy as jnp

from repro.core.distributed import ami_bucketed, pad_rows
from repro.core.star import ami
from repro.launch.mesh import make_test_mesh

_MULTI = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, "src")
from repro.core.distributed import ami_bucketed, pad_rows, shard_rows
from repro.core.star import ami
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(7)
out = []
for n, k, card in [(1000, 4, 13), (97, 3, 2), (4096, 2, 300)]:
    mat = rng.integers(0, card, (n, k)).astype(np.int32)
    padded, n_real = pad_rows(mat, 4)
    dev = shard_rows(padded, mesh)
    valid = jnp.arange(padded.shape[0]) < n_real
    with mesh:
        a = int(ami_bucketed(dev, valid, mesh, dp_axes=("data",)))
    out.append([a, ami(mat)])
print(json.dumps(out))
'''


def test_single_device_exact():
    mesh = make_test_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(1)
    for n, k, card in [(64, 3, 4), (513, 4, 11)]:
        mat = rng.integers(0, card, (n, k)).astype(np.int32)
        padded, n_real = pad_rows(mat, 4)
        valid = jnp.arange(padded.shape[0]) < n_real
        with mesh:
            a = int(ami_bucketed(jnp.asarray(padded), valid, mesh))
        assert a == ami(mat)


def test_multi_shard_exact():
    r = subprocess.run([sys.executable, "-c", _MULTI], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-1500:]
    pairs = json.loads(r.stdout.strip().splitlines()[-1])
    for a, b in pairs:
        assert a == b, pairs
