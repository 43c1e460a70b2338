"""Pallas TPU kernels that read/write the paged KV pools *in place*.

The paged serving pool stores every KV stream as a page pool
``(n_pages, G, KVH*d, 128)`` -- page id ``p`` holds one 128-token,
MX-tile-aligned chunk with its tokens on the lanes, ``G`` is the
scan-over-layers stack.  Stored so, a page is lane-dense at any head width,
the TPU keeps the pool in the layout the kernels read, and the decode step
copies no pool into another layout.  (The streams' logical shape is
``(n_pages, G, 128, KVH, d)``, the ``QuantizedTensor``'s ``shape``.)
Until these kernels existed, every decode step gathered the full context
out of the pools into a dense cache tree and scattered one token back,
tripling the decode path's own DRAM traffic (the opposite of Pimba's
premise that decode is bandwidth-bound, paper §3).

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
    Writes the new token's already-quantized K/V payload column into its
    page slot ``pool[bt[b, len//128], g, :, len%128]`` in place via
    ``input_output_aliases`` -- the software analogue of the PIM
    read-modify-write of a single DRAM column: the kernel rewrites the one
    page that holds the slot, not the whole pool.

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
    k_pool: F.QuantizedTensor,      # pools of logical shape (P, G, 128, KVH, dk)
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
    P, G, TB, KVH, dkc = k_pool.shape
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
    """Rewrite each row's page with its new-token column at the slot."""
    n = len(refs) // 3
    val_refs, page_refs, out_refs = refs[:n], refs[n:2 * n], refs[2 * n:]
    slot = slot_ref[pl.program_id(0)]
    for v_ref, p_ref, o_ref in zip(val_refs, page_refs, out_refs):
        page = p_ref[0, 0].astype(jnp.int32)                  # (R, 128)
        lane = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
        col = v_ref[0].astype(jnp.int32)                      # (R, 1)
        o_ref[0, 0] = jnp.where(lane == slot, col, page).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mx_paged_kv_append(
    pools: Sequence[jnp.ndarray],   # each (P, G, R, 128), R = KVH * w
    rows: Sequence[jnp.ndarray],    # each (B, KVH, w) quantized payload rows
    bt: jnp.ndarray,                # (B, npg) int32
    group,                          # () int32
    lengths: jnp.ndarray,           # (B,) append position per row
    *, interpret: bool,
) -> Tuple[jnp.ndarray, ...]:
    """Write one token's payload columns into their page slots in place.

    The pools are aliased input->output (``input_output_aliases``), so the
    pages no row appends to are never touched -- the paged analogue of the
    dense path's full-cache scatter, at one page a row.  Each row's page
    and slot are computed here and scalar-prefetched as flat ``(B,)``
    vectors: an index map that looked the page up in the 2-D block table at
    a data-dependent column halted the TPU (bad SMEM address).  Rows that
    share a page (idle rows, on the scratch page) race; live rows append to
    pages of their own.
    """
    pools = tuple(pools)
    rows = tuple(r.reshape(r.shape[0], -1, 1) for r in rows)
    assert len(pools) == len(rows) and pools
    B = bt.shape[0]
    P, G, _, TB = pools[0].shape
    assert TB == PAGE_TOKENS
    pos = lengths.astype(jnp.int32)
    page = bt[jnp.arange(B), pos // TB]
    grp = jnp.asarray(group, jnp.int32).reshape(1)

    def at_page(b, page_ref, slot_ref, g_ref):
        return (page_ref[b], g_ref[0], 0, 0)

    n = len(pools)
    page_block = lambda p: pl.BlockSpec((1, 1, p.shape[2], TB), at_page)
    in_specs = (
        [pl.BlockSpec((1, r.shape[1], 1), lambda b, *_: (b, 0, 0))
         for r in rows]
        + [page_block(p) for p in pools])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=in_specs,
        out_specs=[page_block(p) for p in pools],
    )
    out = pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # alias pool i (input index: 3 scalars + n value rows + i) to out i
        input_output_aliases={3 + n + i: i for i in range(n)},
        interpret=interpret,
        name="spu_kv_append",
    )(page, pos % TB, grp, *rows, *pools)
    return tuple(out)
