"""The collective face of the integer tiers is the reduce face.

``collective_mean`` (and ``collective_weighted_mean`` /
``collective_moments``, which are built from it) runs each integer tier
through its ``Policy``, the same prepare / merge / finalize path as
``repro.reduce``'s ``shard_map`` backend.  So the mean of one row per
device must be bitwise the ``op="mean"`` reduction of those rows on the
same mesh, for every integer tier and on every kind of stream, whether
each device's leaf is one row or a matrix of rows, and however the
collective lays the leaf out in rows and chunks.

The cases run in one subprocess with 8 simulated CPU devices (XLA_FLAGS
must be set before jax initializes), started once per module.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
INT_POLICIES = ("exact", "exact2", "procrastinate")
#: 8 rows x 256: normal values scaled by 10^u, u uniform in [-6, 3); rows
#: that cancel to near zero; off-grid values near 1/3
STREAMS = ("scaled", "cancelling", "third")

SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro import reduce as R
from repro.reduce import collective as C

rng = np.random.default_rng(0)
scaled = (rng.standard_normal((8, 256))
          * 10.0 ** rng.uniform(-6, 3, (8, 256))).astype(np.float32)
head = rng.standard_normal((7, 256)) * 10.0 ** rng.uniform(-2, 2, (7, 256))
cancelling = np.concatenate([head, -head.sum(0, keepdims=True)]) \
    .astype(np.float32)
third = (np.float32(1 / 3)
         * (1.0 + 1e-3 * rng.standard_normal((8, 256)))).astype(np.float32)

mesh = Mesh(np.asarray(jax.devices()), ("data",))
zeros = jnp.zeros(8, jnp.int32)
for name, rows in (("scaled", scaled), ("cancelling", cancelling),
                   ("third", third)):
    rows = jnp.asarray(rows)
    for pol in ("exact", "exact2", "procrastinate"):
        want = np.asarray(R.reduce(rows, segment_ids=zeros, num_segments=1,
                                   op="mean", policy=pol,
                                   backend="shard_map", mesh=mesh))[0]
        got, default = [], (C._ROW_LANES, C._CHUNK_ROWS)
        # each device's leaf as one row of 256 and as 4 rows of 64, laid
        # out whole, and in rows of 24 lanes (the last one padded) taken
        # 4 rows a chunk
        for lanes, chunk in (default, (24, 4)):
            C._ROW_LANES, C._CHUNK_ROWS = lanes, chunk
            for leaf in ((1, 256), (4, 64)):
                f = lambda x, pol=pol: R.collective_mean(x[0], ("data",),
                                                         policy=pol)[0]
                mean = jax.jit(jax.shard_map(
                    f, mesh=mesh, in_specs=P("data", None, None),
                    out_specs=P(), check_vma=False))
                got.append(np.asarray(mean(rows.reshape((8,) + leaf))))
        C._ROW_LANES, C._CHUNK_ROWS = default
        got = np.concatenate([g.reshape(-1) for g in got])
        diff = int(np.sum(got != np.tile(want, 4)))
        print(f"CASE {pol} {name} {int(diff == 0)} {diff}")
"""


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", SNIPPET], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return {(p, s): (int(bit), int(diff)) for _, p, s, bit, diff in
            (ln.split() for ln in r.stdout.splitlines()
             if ln.startswith("CASE "))}


@pytest.mark.parametrize("stream", STREAMS)
@pytest.mark.parametrize("policy", INT_POLICIES)
def test_collective_mean_is_the_reduce_mean(cases, policy, stream):
    bit, diff = cases[(policy, stream)]
    assert bit == 1, f"{diff} of 1024 elements differ"
