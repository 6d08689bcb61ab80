"""repro.core — the paper's contribution as composable JAX modules.

The public front door for reductions is ``repro.reduce`` (one call,
accuracy policies, registered backends, the streaming Accumulator
protocol); this package holds the primitives it is built from.

Faithful layer:
  circuit.JugglePAC / circuit.INTAC      cycle-accurate simulators
  circuit_jax.jugglepac_scan             the same FSM as a lax.scan

Production (TPU-native) layer:
  trees        fixed pairing-tree reduction schedules
  segmented    segmented-reduction math oracle + flash-partial combines
               (the blocked schedule itself lives in repro.reduce.backends)
  intac        the integer number formats — scale grid, limbs, exponent
               bins — and the compressed collective; each integer tier's
               one implementation is its repro.reduce Policy
  juggler      bounded-slot streaming gradient accumulation (surfaced as
               repro.reduce.TreeAccumulator)
"""

from . import circuit, circuit_jax, intac, juggler, segmented, trees  # noqa: F401
from .circuit import INTAC, JugglePAC, jugglepac_min_set_size  # noqa: F401
from .intac import (compressed_psum_mean, intac_sum,  # noqa: F401
                    limb_add, limb_finalize, limb_init, limb_merge,
                    limbs_canonical)
from .juggler import (juggler_finalize, juggler_init,  # noqa: F401
                      juggler_push, num_slots_for)
from .segmented import (combine_flash_partials_tree, flash_partial_combine,  # noqa: F401
                        segment_mean, segment_sum_ref,
                        segments_from_lengths)
from .trees import pairwise_tree_sum, pairwise_tree_sum_pytree, tree_combine  # noqa: F401
