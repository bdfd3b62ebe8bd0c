"""Erasure coding package.

Submodules stay import-light from here on purpose — encoder/stream pull
the device coder stack. This namespace only hosts the shard-PRESENCE
accounting helpers shared by the master topology, the health plane, and
the shell: pure bit twiddling on the `shard_bits` word every EC
registration message carries (reference erasure_coding/ec_shard_bits).
"""

from __future__ import annotations

# shard ids live in a uint32 bitmask on the wire (master.proto
# ec_index_bits); 32 is the hard ceiling for any RS(k,m) we speak
MAX_SHARD_ID = 32


def shard_ids(bits: int) -> list[int]:
    """Shard ids present in a shard_bits word, ascending."""
    return [sid for sid in range(MAX_SHARD_ID) if bits >> sid & 1]


def shard_count(bits: int) -> int:
    """Number of shards present in a shard_bits word."""
    return (bits & ((1 << MAX_SHARD_ID) - 1)).bit_count()
