"""Multi-device reduction through the front door: the shard_map backend.

Simulates an 8-device fleet on CPU (the XLA host-platform trick — the
env var must be set before jax initializes), streams one segmented
reduction through ``backend="shard_map"`` at 1/2/8 shards, and asserts
the invariants: ``procrastinate`` reproduces the single-device
``blocked`` schedule **bit for bit** at every shard count, even with
uneven shards; ``exact2`` reproduces the *canonical int32 limbs* bit for
bit while its finalized float — which folds the exactly-captured
quantization-residual limb in device order — stays at ulp-level
agreement.  The float tiers keep tolerance, not bits — the demo prints
all of it.

    PYTHONPATH=src python examples/multi_device_reduce.py
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402
from jax.sharding import Mesh                                 # noqa: E402

import repro                                                  # noqa: E402
from repro import reduce as R                                 # noqa: E402
from repro.core import intac                                  # noqa: E402


def main():
    devs = jax.devices()
    print(f"devices: {len(devs)} x {devs[0].platform}")

    # uneven on purpose: 10_007 rows never divide evenly into 8 shards of
    # 512-row blocks — the backend pads with OUT_OF_RANGE_LABEL rows,
    # which drop out of every sum and count
    rng = np.random.RandomState(0)
    n, d, s = 10_007, 32, 5
    vals = jnp.asarray(rng.randn(n, d).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, s, n))

    base = {p: np.asarray(repro.reduce(vals, segment_ids=ids,
                                       num_segments=s, policy=p,
                                       backend="blocked"))
            for p in ("fast", "exact2", "procrastinate")}

    # exact2's limb-level reference: the canonical int32 hi/lo pair out
    # of the single-device schedule
    pol2 = R.get_policy("exact2")
    mids = R.mask_out_of_range(ids, s)
    domain, _ = pol2.prepare(jnp.where((mids >= 0)[:, None], vals, 0.0), n)
    cb = R.get_backend("blocked").run(domain, mids, s, policy=pol2)
    limbs_base = [np.asarray(v)
                  for v in intac.limbs_canonical(cb[0], cb[1])]

    print(f"\n{n} rows x {d} features -> {s} segments; "
          f"single-device 'blocked' schedule is the reference")
    for nshards in (1, 2, 8):
        mesh = Mesh(np.asarray(devs[:nshards]), ("shards",))
        for pol in ("fast", "exact2", "procrastinate"):
            out = np.asarray(repro.reduce(vals, segment_ids=ids,
                                          num_segments=s, policy=pol,
                                          backend="shard_map", mesh=mesh))
            bitwise = np.array_equal(base[pol], out)
            maxdiff = float(np.abs(base[pol] - out).max())
            line = (f"  shards={nshards}  policy={pol:13s}  "
                    f"bitwise={str(bitwise):5s}  max|diff|={maxdiff:.2e}")
            if pol == "procrastinate":
                assert bitwise, "procrastinate must reproduce the bits"
            elif pol == "exact2":
                csh = R.get_backend("shard_map").run(
                    domain, mids, s, policy=pol2, mesh=mesh)
                limbs_ok = all(
                    np.array_equal(a, np.asarray(b)) for a, b in
                    zip(limbs_base, intac.limbs_canonical(csh[0], csh[1])))
                assert limbs_ok, "exact2 limbs must reproduce the bits"
                assert maxdiff <= 1e-6 * float(np.abs(base[pol]).max())
                line += f"  limbs_bitwise={limbs_ok}"
            else:
                assert maxdiff <= 1e-5 * float(np.abs(base[pol]).max())
            print(line)

    # auto-selection: an active multi-device mesh is enough — no backend
    # argument, no mesh argument
    with jax.set_mesh(Mesh(np.asarray(devs), ("shards",))):
        assert R.select_backend(
            R.get_policy("procrastinate")).name == "shard_map"
        auto = np.asarray(repro.reduce(vals, segment_ids=ids,
                                       num_segments=s,
                                       policy="procrastinate"))
    assert np.array_equal(auto, base["procrastinate"])
    print("\nauto-selection under `jax.set_mesh` picked shard_map and "
          "reproduced the single-device bits — scaling out is a context "
          "manager, not a rewrite")


if __name__ == "__main__":
    main()
