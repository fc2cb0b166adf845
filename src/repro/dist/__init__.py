"""Distributed execution layer.

Submodules:

* ``context``     — ``DistContext`` (axis roles + sharding knobs),
                    ``make_dist``/``no_dist`` constructors.
* ``sharding``    — PartitionSpec sanitation (``sanitize_specs``) and
                    pytree -> ``NamedSharding`` mapping (``tree_shardings``).
* ``collectives`` — ``compressed_allreduce`` (int8 + error feedback) and
                    ``hierarchical_allreduce`` (pod-aware rs/ar/ag).
* ``pipeline``    — ``gpipe_apply`` microbatched pipeline parallelism.
"""
from repro.dist.context import DistContext, make_dist, no_dist
from repro.dist.sharding import sanitize_specs, tree_shardings

__all__ = ["DistContext", "make_dist", "no_dist", "sanitize_specs",
           "tree_shardings"]
