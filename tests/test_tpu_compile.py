"""The main path's TPU programs, compiled for a described v5e chip.

Nothing runs here: the chip's compiler (installed with JAX) compiles
for a v5e that is described, not attached, and refuses what the chip
would refuse — misaligned blocks, scalar stores to vector memory, more
scoped VMEM than a kernel may use. Interpret mode on the CPU shows none
of that. The widths are the deployment's own (84 nodes; 3072 jobs is
the streaming pool, padded; 65536 the monolithic trace).

The topology is described inside a module-scoped fixture and never
while a module is imported: one process at a time may load the TPU
library, and every test worker imports every test file. Where it cannot
be described, the tests skip from the fixture. The persistent
compilation cache is off around these compiles (an entry written here
could not be read back without a chip).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.core import sim_jax
from repro.kernels import ops
from repro.kernels import schedule_step as kss

M = 84


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"      # no compiler logs on disk
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:             # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture
def native_kernels(monkeypatch):
    """Trace the Pallas kernels as the chip would (not in interpret
    mode), with jit caches cleared on both sides so no CPU test reuses
    a native trace and no native compile reuses an interpreted one."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _pass_args(sharding, J, batch=()):
    f32, i32, b = jnp.float32, jnp.int32, jnp.bool_
    shapes = [((J, 3), f32), ((J,), f32), ((J,), i32), ((J,), f32),
              ((J, M), b), ((M, 3), f32), ((M, 3), f32), ((J,), b),
              ((J,), b), ((J,), b), ((3,), f32), ((3,), f32), ((), f32),
              ((), f32), ((), f32)]
    return [jax.ShapeDtypeStruct(batch + s, d, sharding=sharding)
            for s, d in shapes]


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("J,batch", [(3072, ()), (65536, ()), (3072, (4,))],
                         ids=["stream-pool", "monolithic", "vmap4"])
def test_schedule_step_pallas_compiles(one_chip, J, batch):
    fn = functools.partial(kss.schedule_step_pallas, interpret=False)
    if batch:
        fn = jax.vmap(fn)
    compiled = jax.jit(fn).lower(*_pass_args(one_chip, J, batch)).compile()
    assert _has_kernel(compiled)


def test_sim_loop_pallas_compiles(one_chip, native_kernels):
    """The whole ``sim_jax`` replay loop with the fused pass on the
    kernel, at 84 nodes x 4096 jobs."""
    J = 4096
    cfg = api.make_config("fitgpp", n_jobs=J, n_nodes=M,
                          score_backend="pallas")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    jobs = sim_jax.Jobs(
        submit=sds((J,), jnp.int32), exec_total=sds((J,), jnp.int32),
        demand=sds((J, 3), jnp.float32), is_te=sds((J,), jnp.bool_),
        gp=sds((J,), jnp.int32), width=sds((J,), jnp.int32),
        valid=sds((J,), jnp.bool_), akey=None)
    compiled = sim_jax._run_jit_full.lower(
        cfg, jobs, sds((), jnp.int32), "event", False, 0).compile()
    assert _has_kernel(compiled)
