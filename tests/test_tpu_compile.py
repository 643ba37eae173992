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
import re

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


def _layout_copies(hlo: str, J: int) -> list:
    """The ``copy`` ops of an optimised HLO text that change the layout
    of a slots x nodes tile or of the slots x 3 demand: what the
    compiler inserts where two ops disagree on which axis lies on the
    lanes. Copies that keep the layout (a move between memory spaces,
    a copy before an in-place update) are not counted."""
    op = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]"
                    r"\{([\d,]*)")
    layout = {}
    for line in hlo.splitlines():
        m = op.match(line)
        if m:
            layout[m.group(1)] = m.group(4)
    tiles = (sorted((J, M)), sorted((J, 3)))
    found = []
    for line in hlo.splitlines():
        m = op.match(line)
        if not m or " copy(%" not in line:
            continue
        dims = sorted(int(d) for d in m.group(3).split(",") if d)
        src = re.search(r" copy\(%([\w.\-]+)\)", line).group(1)
        if dims in tiles and layout.get(src) != m.group(4):
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("program", ["replay", "stream-round"])
def test_loop_keeps_one_tile_layout(one_chip, program):
    """The engine loop holds its slots x nodes tiles in one layout
    (DESIGN.md §7): the compiled replay and stream-round programs of
    the benchmark's configuration (fitgpp, s = 4, P = 1, jnp pass,
    event time) transpose no such tile, nor the demand, inside the
    loop. At most the loop's entry and exit transposes of ``assign``
    may show as copies. Slot-major tiles gave 19 and 20 such copies
    here, at 512 slots as at the cells' sizes."""
    J = 512
    cfg = api.make_config("fitgpp", n_jobs=J, n_nodes=M, s=4.0, P=1,
                          score_backend="jnp")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    akey = sds((J,), jnp.float32) if program == "stream-round" else None
    jobs = sim_jax.Jobs(
        submit=sds((J,), jnp.int32), exec_total=sds((J,), jnp.int32),
        demand=sds((J, 3), jnp.float32), is_te=sds((J,), jnp.bool_),
        gp=sds((J,), jnp.int32), width=sds((J,), jnp.int32),
        valid=sds((J,), jnp.bool_), akey=akey)
    if program == "replay":
        lowered = sim_jax._run_jit_full.lower(
            cfg, jobs, sds((), jnp.int32), "event", False, 0)
    else:
        st = jax.eval_shape(
            lambda jb: sim_jax.init_state(jb, M, cfg.cluster.node.as_tuple(),
                                          0), jobs)
        st = jax.tree.map(lambda x: sds(x.shape, x.dtype), st)
        lowered = sim_jax._run_round_jit.lower(
            cfg, jobs, st, sds((), jnp.int32), "event", False)
    copies = _layout_copies(lowered.compile().as_text(), J)
    assert len(copies) <= 2, "\n".join(copies)
