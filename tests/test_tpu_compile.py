"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses here what it would refuse on the chip:
misaligned Pallas blocks, kernels it cannot partition, programs that do
not fit the device.  Each test compiles one kernel or jitted lowering at
the bucket shapes the paper-scale sensor graph reaches and checks that
the Pallas kernels (``tpu_custom_call``) or the collective are in the
compiled program.  Nothing runs, so nothing here is a time.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import sweep
from repro.core.distributed import ami_bucketed_batch
from repro.kernels import ops
from repro.kernels.seg_count import seg_boundaries
from repro.kernels.sig_hash import sig_hash
from repro.query.batch import _match_fn

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without that chip: keep it out of the cache
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            cc.reset_cache()
            sweep.clear_compile_cache()


@pytest.fixture
def one_chip(topo, monkeypatch):
    """Shape factory on one described chip; kernels compile for real
    (``kernels.ops`` would pick interpret mode from the CPU backend)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    sharding = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)
    return shape


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used} bytes do not fit one v5e"
    return compiled.as_text()


@pytest.mark.parametrize("dims", [(262144, 2), (262144, 4), (262144, 8),
                                  (262144, 16), (8, 262144, 2)])
def test_sig_hash_compiles(one_chip, dims):
    text = _compile(lambda m: sig_hash(m, interpret=False),
                    one_chip(dims, jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dims", [(262144, 2), (8, 262144, 2),
                                  (256, 4096, 2)])
def test_seg_boundaries_compiles(one_chip, dims):
    text = _compile(lambda s: seg_boundaries(s, interpret=False),
                    one_chip(dims, jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_b,k_b,c_b", [(262144, 8, 8), (4096, 16, 256)])
def test_bucket_sweep_compiles(one_chip, n_b, k_b, c_b):
    i32 = jnp.int32
    text = _compile(sweep._bucket_sweep_fn(True),
                    one_chip((n_b, k_b), i32), one_chip((n_b,), jnp.bool_),
                    one_chip((c_b, k_b), i32), one_chip((), i32),
                    one_chip((c_b,), i32), one_chip((), i32))
    assert "tpu_custom_call" in text


def test_query_match_compiles(one_chip):
    m_b, k_b, q_b = 4096, 4, 256
    i32 = jnp.int32
    text = _compile(_match_fn(True), one_chip((m_b, k_b), i32),
                    one_chip((m_b,), jnp.bool_), one_chip((q_b, k_b), i32),
                    one_chip((q_b, k_b), i32))
    assert "tpu_custom_call" in text


def test_ami_bucketed_batch_compiles_over_four_chips(topo, monkeypatch):
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    n, k, c = 4096, 8, 8

    def shaped(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype,
                                    sharding=NamedSharding(mesh, spec))
    text = _compile(
        lambda m, v, masks: ami_bucketed_batch(m, v, masks, mesh,
                                               dp_axes=("data",)),
        shaped((n, k), jnp.int32, P("data", None)),
        shaped((n,), jnp.bool_, P("data")),
        shaped((c, k), jnp.int32, P(None, None)))
    assert "all-to-all" in text
    assert "tpu_custom_call" in text
