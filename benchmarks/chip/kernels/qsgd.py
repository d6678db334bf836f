"""Bytes the qsgd codec must move in HBM, per round and per device, on the
cell's parameter leaves.

A leaf of n values is cut into nb = ceil(n / bucket) buckets of v = nb *
bucket values (the last one zero-padded). Per leaf and round:

- quantize reads the values and their uniform draws (f32) and writes int8
  levels and one f32 norm per bucket;
- dequantize (with error feedback: the local image of the payload) reads
  the levels and norms and writes f32 values;
- dequant-reduce reads all P peers' levels and norms and the P mixing
  weights, and writes the f32 mean.

All three are elementwise with a few operations per byte, so HBM bandwidth
bounds them.
"""


def quantize_bytes(v, nb):
    return 4 * v + 4 * v + v + 4 * nb


def dequantize_bytes(v, nb):
    return v + 4 * nb + 4 * v


def dequant_reduce_bytes(v, nb, peers):
    return peers * v + 4 * peers * nb + 4 * peers + 4 * v


def round_bytes(leaf_sizes, *, peers, bucket, ef):
    total = 0
    for n in leaf_sizes:
        nb = -(-n // bucket)
        v = nb * bucket
        total += quantize_bytes(v, nb) + dequant_reduce_bytes(v, nb, peers)
        if ef:
            total += dequantize_bytes(v, nb)
    return total

