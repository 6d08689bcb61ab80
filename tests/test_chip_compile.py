"""Compile the reduce kernel for a described TPU v5e, without a chip.

The TPU compiler ships with jaxlib and compiles for a topology that is
described rather than attached, so these tests refuse — here, at no chip
time — what Mosaic would refuse on the chip: primitives with no TPU
lowering (``optimization_barrier``, ``scatter-add``), int32 x int32
matmuls the v5e MXU does not take, misaligned tiles.  Interpret mode
cannot see any of those.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test
worker imports this file.  Keep these tests in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import reduce as R
from repro.reduce import api

POLICIES = ("fast", "compensated", "exact", "exact2", "procrastinate")


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("num_segments", [8, 64])
@pytest.mark.parametrize("policy", POLICIES)
def test_pallas_backend_compiles_for_v5e(one_chip, policy, num_segments):
    """Every tier, on both sides of the lane-form crossover, compiles to
    a Mosaic kernel at the width the chip smoke run uses."""
    n, d = 1 << 16, 128

    def f(v, i):
        return R.reduce(v, segment_ids=i, num_segments=num_segments,
                        policy=policy, backend="pallas", interpret=False)

    text = _compiled_text(f, one_chip, ((n, d), jnp.float32),
                          ((n,), jnp.int32))
    assert "tpu_custom_call" in text


def test_serving_logprob_mean_compiles_for_v5e(one_chip):
    """The reduction ``Engine`` retires requests through: a segmented
    compensated mean over a flat (step x slot) logprob stream, D=1."""
    def f(v, i):
        return R.reduce(v, segment_ids=i, num_segments=8, op="mean",
                        policy="compensated", backend="pallas",
                        interpret=False)

    text = _compiled_text(f, one_chip, ((8 * 33,), jnp.float32),
                          ((8 * 33,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n, d, s",
                         [(1_605_972, 128, 340), (200_704, 1024, 1)],
                         ids=["gradsq", "global_norm_leaf"])
def test_exact2_sumsq_maps_its_domain_in_the_kernel(one_chip, n, d, s):
    """exact2 ``op="sumsq"`` calls as an eager ``reduce`` runs them (the
    square, ``pre``, is a program of its own) compile to one Mosaic
    kernel that maps each block into the 8·D domain in VMEM, and no
    N x 8·D array is left in the program: the benchmark's
    stablelm-1.6b-gradsq shard (1,605,972 x 128 f32 rows in 340 sets;
    12.25 GiB of temporaries when the domain was built for the whole
    stream first), and ``global_norm``'s rows of 1024 for the embedding
    of stablelm-1.6b (refused for VMEM when the kernel read the
    domain)."""
    spec = R.ReduceSpec(op="sumsq", policy="exact2", backend="pallas",
                        interpret=False)
    args = [jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)]
    compiled = api._dispatch.lower(*args, spec=spec, num_segments=s,
                                   segmented=s > 1, squeeze_d=False).compile()
    assert compiled.as_text().count('"tpu_custom_call"') == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2 ** 30


def test_dense_decode_writes_its_caches_in_place(one_chip):
    """The serving decode of a dense configuration (the stablelm-1.6b
    smoke preset at four layers, so that its layers run in a scan)
    compiled for a v5e: its output caches are its input caches (donated,
    aliased), and no select or copy spans the stacked cache, so the step
    writes its new rows where they lie instead of rewriting the cache."""
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.serve.engine import Engine

    cfg = get_smoke_config("stablelm-1.6b").scaled(n_layers=4)
    b, max_len = 4, 256
    eng = Engine(cfg, None, max_len=max_len, max_batch=b)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = shaped(jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    caches = shaped(eng._caches)
    i32 = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    compiled = eng._decode.lower(
        params, jax.ShapeDtypeStruct((b, 1), jnp.int32, sharding=one_chip),
        caches, i32,
        jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one_chip)).compile()
    kv = jax.tree.leaves(caches)[:2]
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        sum(a.size * a.dtype.itemsize for a in kv)
    text = compiled.as_text()
    full = re.escape(f"{kv[0].dtype.name.replace('float', 'f')}"
                     f"[{','.join(map(str, kv[0].shape))}]")
    assert not re.search(rf"= {full}\S* (select|copy)\(", text)


@pytest.mark.parametrize("policy", ("exact", "exact2", "procrastinate"))
def test_collective_mean_fits_an_embedding_leaf(topology, policy):
    """``collective_mean``'s integer tiers on a 2x2 v5e, one 100352 x 2048
    f32 leaf a chip (stablelm-1.6b's embedding, 0.77 GiB): the domain is
    mapped chunk by chunk, so the temporaries stay under two leaves'
    worth.  Mapped whole, exact2's domain and carries needed 26 GB of a
    chip's 16 GB (20.7 GB through the earlier three-limb psum)."""
    mesh = Mesh(np.asarray(topology.devices).reshape(4), ("data",))
    leaf = (100352, 2048)
    x = jax.ShapeDtypeStruct((4,) + leaf, jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    f = jax.jit(jax.shard_map(
        lambda g: R.collective_mean(g[0], ("data",), policy=policy)[0],
        mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False))
    temp = f.lower(x).compile().memory_analysis().temp_size_in_bytes
    assert temp < 2 * 4 * leaf[0] * leaf[1]
