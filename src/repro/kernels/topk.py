"""Top-k select+pack Pallas TPU kernel.

The sparsified exchange ships only the k largest-|x| entries of each
gradient leaf as (value, int32 index) pairs. ``lax.top_k`` sorts the whole
vector (O(n log n) and an awkward fit for the VPU); the encoder instead
finds the k-th magnitude by **bisection on its bit pattern**: the int32
patterns of non-negative floats order like the floats, so 31 halvings of
[0, +inf] pin the exact k-th largest |x|. Each halving is one tiled pass
of compare+popcount over the leaf (O(n) VPU work, no sort); the bracket
lives in SMEM across the grid, whose outer axis is the halving step and
inner axis the (rows, 128) tile. The leaf never has to fit VMEM at once.

Packing the survivors into dense (k,) banks is a compaction (a prefix
count and a gather), which Mosaic cannot express; it runs in XLA around
the kernel. Ties at the threshold are resolved in two tiers so the output
is exactly k entries: everything strictly above the threshold is kept,
and the remaining slots go to threshold-magnitude entries in ascending
index order. For distinct magnitudes this selects exactly ``lax.top_k``'s
set; on exact magnitude ties only the tie-break order may differ (the
decoded dense tensor is identical when tied values are equal). Entries
come out in ascending index order.

The decoder has no kernel: it is XLA's scatter-add
(``ops.topk_scatter_accum``), since Mosaic has no scatter and XLA's
scatter-add already folds the P*k weighted entries into the dense sum
in one pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128  # lane width: flat vectors are tiled to (rows, LANE)
MAX_TILE_ROWS = 256  # rows per tile: (256, 128) f32 = 128 KiB of VMEM
_BISECT_STEPS = 31  # |x| bit patterns lie in [0, 0x7F800000]
_HI0 = 0x7F800001  # one past +inf: no finite magnitude reaches it


def _tiling(n: int):
    """(rows per tile, total padded rows) for a flat length-n vector."""
    rows = -(-n // LANE)
    rows += (-rows) % 8
    tr = min(MAX_TILE_ROWS, rows)
    return tr, rows + (-rows) % tr


def _threshold_kernel(bits_ref, thr_ref, st_ref, *, k: int):
    """Grid (step, tile): count |x| >= mid over all tiles, then halve."""
    step, t = pl.program_id(0), pl.program_id(1)

    @pl.when((step == 0) & (t == 0))
    def _():
        st_ref[0] = jnp.int32(0)  # lo: count(bits >= lo) >= k
        st_ref[1] = jnp.int32(_HI0)  # hi: count(bits >= hi) < k

    @pl.when(t == 0)
    def _():
        st_ref[2] = jnp.int32(0)

    lo, hi = st_ref[0], st_ref[1]
    mid = lo + ((hi - lo) >> 1)
    st_ref[2] += jnp.sum((bits_ref[...] >= mid).astype(jnp.int32))

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        big = st_ref[2] >= k
        new_lo = jnp.where(big, mid, lo)
        st_ref[0] = new_lo
        st_ref[1] = jnp.where(big, hi, mid)
        thr_ref[0, 0] = new_lo


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_select_pack(x: jnp.ndarray, k: int, *, interpret: bool):
    """x: (n,) f32 -> (values f32 (k,), indices int32 (k,)) of the k largest |x|."""
    n = x.shape[0]
    assert 1 <= k <= n, f"k={k} out of range for n={n}"
    tr, rows = _tiling(n)
    flat = x.astype(jnp.float32)
    bits = lax.bitcast_convert_type(jnp.abs(flat), jnp.int32)
    padded = jnp.pad(bits, (0, rows * LANE - n), constant_values=-1)  # never selected
    thr = pl.pallas_call(
        functools.partial(_threshold_kernel, k=k),
        grid=(_BISECT_STEPS, rows // tr),
        in_specs=[pl.BlockSpec((tr, LANE), lambda s, t: (t, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        scratch_shapes=[pltpu.SMEM((3,), jnp.int32)],
        interpret=interpret,
    )(padded.reshape(rows, LANE))[0, 0]
    # compaction (XLA): all entries above the threshold, then the first
    # threshold-magnitude entries in index order up to exactly k
    sure = bits > thr
    edge = bits == thr
    fill = k - jnp.sum(sure.astype(jnp.int32))
    take = sure | (edge & (jnp.cumsum(edge.astype(jnp.int32)) <= fill))
    idx = jnp.nonzero(take, size=k)[0].astype(jnp.int32)
    return flat[idx], idx
