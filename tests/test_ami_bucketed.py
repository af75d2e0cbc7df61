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


_EXCHANGE = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import functools, sys, json
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, "src")
from repro.api import Compactor
from repro.core import distributed, sweep
from repro.core.distributed import ami_bucketed_batch, shard_rows
from repro.core.star import ami
from repro.data.synthetic import SensorGraphSpec, generate
from repro.kernels import ops as kops
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 1), ("data", "model"))
out = {"batch": []}

# the batch's answer against the exchange replayed on the host: shard s
# holds rows [s*nl, (s+1)*nl); a signature goes to shard sig_hi % 4 and
# is kept if fewer than cap of its shard's earlier rows went there
rng = np.random.default_rng(11)
n, nl = 1024, 256
mat = rng.integers(0, 3, (n, 4)).astype(np.int32)
masks = np.array([[1, 1, 1, 1], [1, 1, 1, 0], [1, 0, 0, 0], [0, 0, 0, 0]],
                 np.int32)
valid = jnp.ones((n,), bool)
sig = np.asarray(kops.row_signature(jnp.asarray(mat[None] * masks[:, None]),
                                    valid=valid))
for cap_factor in (4.0, 0.25):
    with mesh:
        total, dropped = ami_bucketed_batch(
            shard_rows(mat, mesh), valid, jnp.asarray(masks), mesh,
            cap_factor=cap_factor)
    cap = max(int(cap_factor * nl / 4) + 8, 8)
    want_total, want_dropped = [], []
    for c in range(masks.shape[0]):
        kept, lost = set(), 0
        for s in range(4):
            seen = [0] * 4
            for h, l in sig[c, s * nl:(s + 1) * nl].tolist():
                o = h % 4
                if seen[o] < cap:
                    kept.add((h, l))
                else:
                    lost += 1
                seen[o] += 1
        want_total.append(len(kept) + lost)
        want_dropped.append(lost)
    cols = [np.flatnonzero(m) for m in masks]
    out["batch"].append({
        "cap_factor": cap_factor,
        "total": np.asarray(total).tolist(),
        "dropped": np.asarray(dropped).tolist(),
        "want_total": want_total, "want_dropped": want_dropped,
        "host_ami": [ami(mat[:, c]) if c.size else 1 for c in cols]})

# SHARD_STATS over a whole sharded compaction
calls = []
plain = sweep.ShardedSweepWorkspace._run_batch


def spy(self, stack, n_sp):
    calls.append([self.n_bucket, stack.shape[0]])
    return plain(self, stack, n_sp)


sweep.ShardedSweepWorkspace._run_batch = spy
store = generate(SensorGraphSpec(n_observations=800, seed=3))
for cap_factor in (4.0, 0.25):
    # the sweep builds its program from the module's function: force the
    # exchange's capacity there
    distributed.ami_bucketed_batch = functools.partial(
        ami_bucketed_batch, cap_factor=cap_factor)
    sweep.clear_compile_cache()
    calls.clear()
    Compactor(detector="gfsp", backend="sharded",
              backend_opts={"mesh": mesh}).run(store)
    out[str(cap_factor)] = {"stats": dict(sweep.SHARD_STATS),
                            "calls": list(calls)}
print(json.dumps(out))
'''


def test_sharded_batches_count_shards_bytes_and_dropped_signatures():
    """Four shards: ``ami_bucketed_batch`` returns the dropped signatures
    beside the total, which is still distinct kept + dropped (0 and
    exact at ``cap_factor`` 4, non-zero when forced small), and a sharded
    compaction counts its batches, shards, per-shard exchange bytes
    (``n_shards * c * cap * 2 * 4`` a batch) and drops in ``SHARD_STATS``."""
    r = subprocess.run([sys.executable, "-c", _EXCHANGE],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    exact, small = out["batch"]
    assert exact["dropped"] == [0, 0, 0, 0]
    assert exact["total"] == exact["host_ami"] == exact["want_total"]
    assert small["dropped"] == small["want_dropped"]
    assert sum(small["dropped"]) > 0
    assert small["total"] == small["want_total"]

    st, calls = out["4.0"]["stats"], out["4.0"]["calls"]
    assert st["batches"] == len(calls) > 0
    assert st["shards"] == 4 * st["batches"]
    assert st["exchange_bytes"] == sum(4 * c * (n_b // 4 + 8) * 2 * 4
                                       for n_b, c in calls)
    assert st["dropped"] == 0
    assert out["0.25"]["stats"]["dropped"] > 0


def test_reset_zeroes_shard_stats():
    from repro.core import sweep
    for k in sweep.SHARD_STATS:
        sweep.SHARD_STATS[k] = 7
    sweep.reset_trace_stats()
    assert set(sweep.SHARD_STATS.values()) == {0}
