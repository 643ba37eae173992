"""The cells' job sets, and the reference's answers on them, pinned as
sha1 digests taken before the generator and the reference learnt gang
jobs: one seed of each cell at the cell's own size, two at fewer jobs.
A configuration whose ``gang_share`` is 0 must draw the same bytes, of
width 1, and the reference must answer the same on them."""
import hashlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import gen, program, reference, run  # noqa: E402

# (cell, seed): (jobs, job set digest, reference digest). Seed 5 at the
# cell's own size; the other seeds at fewer jobs, from the same streams.
PINNED = {
    ("paper84-replay", 5): (65536,
                            "8adb2782f7c6aa99b3531fb499a1d30f63f68240",
                            "d582ce1d51b827c9a49f112d75be4318132f461f"),
    ("paper84-replay", 3001600001): (
        8192, "21c50be184876c4e378f84fb5b4cd91221ea8538",
        "49f6aa873012959bad9cadf3e25202a8aa34f367"),
    ("paper84-replay", 2 ** 31 + 16): (
        8192, "47c2cf2a0db43650d34a2887388ae51e690d8c99",
        "dc74ff0b5115106ecbfc2ed5d57c1511be16f375"),
    ("saturn262-stream", 5): (196608,
                              "f48d783aff7b02e0f8a083c1f0425f682cf7cc35",
                              None),
    ("saturn262-stream", 3001600001): (
        16384, "f469b936f2ea3f10ac591eb1e454546013277af7",
        "f13ad8787dbae60d3b566ffe897ed7edffce8c78"),
    ("saturn262-stream", 2 ** 31 + 16): (
        16384, "70eaf37c0f62a164f333b59401b288d667f39a16",
        "65329bc2d1c23d692901b78317dc4e2a94f3bc3e"),
}


def _sha1(*arrays) -> str:
    h = hashlib.sha1()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell,seed", list(PINNED),
                         ids=[f"{c}-{s}" for c, s in PINNED])
def test_job_sets_without_gangs_are_unchanged(cell, seed):
    c = run.load_cell(cell)
    assert c.config["jobs"]["gang_share"] == 0.0
    n, want_jobs, want_ref = PINNED[cell, seed]
    js = gen.build(c.config, dict(c.traffic, jobs=n), seed)
    assert js.n == n
    assert _sha1(js.submit, js.exec_total, js.demand, js.is_te,
                 js.gp) == want_jobs
    assert (js.width == 1).all()
    if want_ref is not None:
        pol = c.config["policy"]
        ref = reference.simulate(js, c.config["cluster"], pol["name"],
                                 pol["s"], pol["P"], program.seed32(seed))
        assert _sha1(ref.finish, ref.preempt_count,
                     ref.last_signal) == want_ref
