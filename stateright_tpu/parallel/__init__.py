"""Multi-chip scale-out for the wave engine.

The TPU-native replacement for the reference's work-stealing job market
(stateright src/job_market.rs:66-147): instead of threads stealing job
batches from a shared stack, every device owns one shard of the
fingerprint space and the BFS frontier, and each wave ends with a
balanced ``all_to_all`` shuffle that routes every candidate successor
to the device that owns its fingerprint — so dedup stays shard-local
and no shared mutable state exists at all. Termination and counters are
``psum`` reductions over the mesh (SURVEY.md §2.5 items 2-4).
"""

from .engine_sortmerge import ShardedSortMergeTpuBfsChecker

__all__ = ["ShardedSortMergeTpuBfsChecker"]
