# Distribution layer: logical-axis sharding context, parameter/state
# sharding tables, shard_map wrapper, split-KV decode attention, and
# pipeline parallelism.  Every entry point degrades to a no-op on a
# single device so the smoke tests never pay for it.
