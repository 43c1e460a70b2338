"""Pallas TPU kernels that read/write the paged KV pools *in place*.

The paged serving pool stores every KV stream as a page pool
``(n_pages, G, 128, KVH, d)`` -- page id ``p`` holds one 128-token,
MX-tile-aligned chunk, ``G`` is the scan-over-layers stack.  Until these
kernels existed, every decode step gathered the full context out of the
pools into a dense cache tree and scattered one token back, tripling the
decode path's own DRAM traffic (the opposite of Pimba's premise that decode
is bandwidth-bound, paper §3).

``PAGE_TOKENS == 128`` was chosen to equal the MX tile, so the flash grid
can walk the block table directly:

``mx_paged_attention_decode``
    Same score -> streaming softmax -> attend pipeline as
    :func:`repro.kernels.mx_attention.mx_attention_decode`, but the grid's
    time dimension walks ``bt[B, npg]``: the block table (and the stacked
    layer index) are **scalar-prefetched**, so each tile's index map
    dequantizes one 128-token page straight out of the shared pool -- no
    dense copy of the context ever exists.  Accumulation order per row is
    identical to the dense kernel (page ``t`` of row ``b`` holds exactly
    tile ``t`` of the gathered layout), so outputs are bit-identical.

``mx_paged_kv_append``
    Writes the new token's already-quantized K/V payload rows into their
    page slot ``pool[bt[b, len//128], g, len%128]`` in place via
    ``input_output_aliases`` -- the software analogue of the PIM
    read-modify-write of a single DRAM column: the kernel moves one row,
    not the whole pool.  (On a TPU, XLA still copies the pools into the
    kernels' row-major layout around the decode step; see PERF.md.)

Both run in interpret mode on the CPU and compiled on a TPU
(:mod:`repro.ops.platform` decides); quantization math is shared with
:mod:`repro.core.formats`, so results match the jnp reference bitwise.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import formats as F
from repro.core.paged import PAGE_TOKENS
from repro.kernels.mx_attention import flash_decode


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "v_width", "scale"),
)
def mx_paged_attention_decode(
    q: jnp.ndarray,                 # (B, H, dk) current-token queries
    k_pool: F.QuantizedTensor,      # pools (P, G, 128, KVH, dk) MX8 payloads
    v_pool: Optional[F.QuantizedTensor],  # like k_pool; None => MLA
    bt: jnp.ndarray,                # (B, npg) int32 physical page ids
    group,                          # () int32 stacked-layer index
    lengths: jnp.ndarray,           # (B,) int32 valid cache length
    *, scale: Optional[float] = None, v_width: Optional[int] = None,
    interpret: bool,
) -> jnp.ndarray:
    """Fused paged decode attention; returns (B, H, dv) f32.

    Bit-identical to ``mx_attention_decode`` over the gathered dense layout
    of the same pages (same tile order, same flash accumulators).
    """
    B, H, dk = q.shape
    P, G, TB, KVH, dkc = k_pool.payload["mantissa"].shape
    assert dk == dkc and H % KVH == 0 and TB == PAGE_TOKENS
    assert v_pool is not None or v_width is not None
    scale = scale if scale is not None else dk ** -0.5
    qg = (q.astype(jnp.float32) * scale).reshape(B, KVH, H // KVH, dk)
    y = flash_decode(qg, k_pool, v_pool, lengths, n_q=1, v_width=v_width,
                     pages=(bt, group), interpret=interpret,
                     name="spu_attn_decode")
    return y.reshape(B, H, -1)


# ---------------------------------------------------------------------------
# in-place paged token append
# ---------------------------------------------------------------------------

def _append_kernel(page_ref, slot_ref, grp_ref, *refs):
    """Write each row's new-token block into its page slot (one column)."""
    n = len(refs) // 3
    val_refs, out_refs = refs[:n], refs[2 * n:]
    # the aliased pools (refs[n:2n]) stay in HBM, unread
    for v_ref, o_ref in zip(val_refs, out_refs):
        o_ref[...] = v_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mx_paged_kv_append(
    pools: Sequence[jnp.ndarray],   # each (P, G, 128, KVH, w)
    rows: Sequence[jnp.ndarray],    # each (B, KVH, w) quantized payload rows
    bt: jnp.ndarray,                # (B, npg) int32
    group,                          # () int32
    lengths: jnp.ndarray,           # (B,) append position per row
    *, interpret: bool,
) -> Tuple[jnp.ndarray, ...]:
    """Scatter one token's payload rows into their page slots in place.

    The pools are aliased input->output (``input_output_aliases``), so the
    unwritten 99.9% of every pool is never touched -- the paged analogue of
    the dense path's full-cache scatter, at one-slot write traffic.  Each
    row's page and slot are computed here and scalar-prefetched as flat
    ``(B,)`` vectors: an index map that looked the page up in the 2-D block
    table at a data-dependent column halted the TPU (bad SMEM address).
    """
    pools = tuple(pools)
    rows = tuple(rows)
    assert len(pools) == len(rows) and pools
    B = bt.shape[0]
    P, G, TB, KVH, _ = pools[0].shape
    assert TB == PAGE_TOKENS
    pos = lengths.astype(jnp.int32)
    page = bt[jnp.arange(B), pos // TB]
    grp = jnp.asarray(group, jnp.int32).reshape(1)

    def slot(b, page_ref, slot_ref, g_ref):
        return (page_ref[b], g_ref[0], slot_ref[b], 0, 0)

    n = len(pools)
    in_specs = (
        [pl.BlockSpec((1, 1, 1, KVH, r.shape[-1]),
                      lambda b, *_: (b, 0, 0, 0, 0)) for r in rows]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n)
    out_specs = [pl.BlockSpec((1, 1, 1, KVH, p.shape[-1]), slot)
                 for p in pools]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # alias pool i (input index: 3 scalars + n value rows + i) to out i
        input_output_aliases={3 + n + i: i for i in range(n)},
        interpret=interpret,
        name="spu_kv_append",
    )(page, pos % TB, grp, *(r.reshape((B, 1, 1) + r.shape[1:]) for r in rows),
      *pools)
    return tuple(out)
