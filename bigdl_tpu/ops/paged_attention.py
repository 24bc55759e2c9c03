"""Paged-attention decode kernel: K/V pages streamed through the page
table (vLLM-style PagedAttention on the flash online-softmax recurrence).

The serving hot path (``serving/paging.py``) stores K/V in a global pool
of fixed-size pages — ``(num_pages, H, page_size, D)`` per layer — and
each slot reaches its tokens through an int32 page table. The XLA
reference path (``parallel/sequence.py``) materializes a dense
``(slots, max_position, D)`` gather of every slot's FULL table row per
layer per step, then runs masked attention over it: O(S·max_position·D)
HBM traffic regardless of how short the streams are.

This kernel never materializes that gather. The grid is
(slot, head-block, page): the page dimension walks one slot's page list
in position order, each step fetching the page's K/V block into VMEM
*directly through the page table* (the BlockSpec index map reads the
scalar-prefetched table, so the DMA engine chases the indirection) and
folding it into flash-attention m/l/acc accumulators held in VMEM
scratch. Sentinel semantics are preserved exactly: a table entry of
``num_pages`` ("no page") clamps to a resident page for the fetch and is
excluded by the mask, so pageless tails and forced-inactive rows
contribute nothing — matching the ``mode="clip"`` + length-mask contract
of the XLA path.

Variants, same kernel schedule:

- **int8** (PR 12 layout): per-(page, head, offset) f32 scale planes are
  fetched through the same index map and the dequantize
  (``int8 * scale``) happens in VMEM — the pool's 1-byte tokens never
  expand in HBM;
- **tensor-parallel** (PR 15 layout): the head-block grid is head-local,
  so the kernel drops into a ``shard_map`` over the tp axis with zero
  collectives — each chip runs the identical kernel on its head shard.

On non-TPU backends the kernels run in pallas interpret mode
(``ops/pallas_util.py``), so the tier-1 parity tests exercise the exact
code path the chip runs. Dispatch is gated by ``BIGDL_TPU_PAGED_KERNEL``
(default off — the XLA gather path, bit-identical to before).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bigdl_tpu.ops.pallas_util import (NEG_INF, compiler_params, fit_block,
                                       use_interpret)


def _online_update(q, k, v, valid, sm_scale, m_scr, l_scr, acc_scr):
    """Fold one page's K/V block into the running (m, l, acc) softmax
    state. q: (hb, C, D); k/v: (hb, page_size, D); valid: (C, page_size)."""
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(valid[None], s, NEG_INF)                # (hb, C, ps)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1)
    acc_scr[:] = acc_scr[:] * alpha[..., None] + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_scr[:] = m_new


def _decode_kernel(pt_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, sm_scale, page_size,
                   num_pages):
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    c = q_ref.shape[1]
    # visibility: key slot j iff j <= start + c (causality and the write
    # frontier in one predicate — the chunk's own K/V was written to the
    # pool before the kernel runs, mirroring the XLA write-then-gather
    # order) AND the table entry is a real page; a fully masked row
    # keeps m at NEG_INF and emits discarded junk, exactly the rows both
    # paths already throw away
    kpos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (c, page_size), 1)
    qpos = start_ref[b] + jax.lax.broadcasted_iota(
        jnp.int32, (c, page_size), 0)
    valid = (kpos <= qpos) & (pt_ref[b, p] < num_pages)
    _online_update(q_ref[:].astype(jnp.float32),
                   k_ref[:].astype(jnp.float32),
                   v_ref[:].astype(jnp.float32),
                   valid, sm_scale, m_scr, l_scr, acc_scr)

    @pl.when(p == pl.num_programs(2) - 1)
    def _emit():
        o_ref[:] = (acc_scr[:]
                    / jnp.maximum(l_scr[:], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def _decode_kernel_quant(pt_ref, start_ref, q_ref, k_ref, v_ref, ks_ref,
                         vs_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         sm_scale, page_size, num_pages):
    """int8 variant: the page's K/V arrive as int8 with their f32 scale
    planes (fetched through the same table index map) and dequantize in
    VMEM — identical arithmetic to ``paged_gather_dequant``."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    c = q_ref.shape[1]
    kpos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (c, page_size), 1)
    qpos = start_ref[b] + jax.lax.broadcasted_iota(
        jnp.int32, (c, page_size), 0)
    valid = (kpos <= qpos) & (pt_ref[b, p] < num_pages)
    k = k_ref[:].astype(jnp.float32) * ks_ref[:][..., None]
    v = v_ref[:].astype(jnp.float32) * vs_ref[:][..., None]
    _online_update(q_ref[:].astype(jnp.float32), k, v, valid, sm_scale,
                   m_scr, l_scr, acc_scr)

    @pl.when(p == pl.num_programs(2) - 1)
    def _emit():
        o_ref[:] = (acc_scr[:]
                    / jnp.maximum(l_scr[:], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def _call_kernel(q, pool, page_table, start, *, sm_scale, head_block,
                 interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, c, d = q.shape
    n, _, ps, _ = pool["k"].shape
    npg = page_table.shape[1]
    # the int8 scale planes are (num_pages, H, page_size): the head block
    # sits on the sublane axis of their BlockSpec
    hb = fit_block(h, head_block, align=8)
    quant = "k_scale" in pool
    kernel = functools.partial(
        _decode_kernel_quant if quant else _decode_kernel,
        sm_scale=sm_scale, page_size=ps, num_pages=n)

    # the indirection: the K/V (and scale) index maps read the
    # scalar-prefetched page table, so each grid step DMAs the page the
    # TABLE names — the sentinel clamps to a resident page whose values
    # the kernel's mask then discards
    def kv_map(bb, hh, pp, pt, st):
        return (jnp.minimum(pt[bb, pp], n - 1), hh, 0, 0)

    def sc_map(bb, hh, pp, pt, st):
        return (jnp.minimum(pt[bb, pp], n - 1), hh, 0)

    def q_map(bb, hh, pp, pt, st):
        return (bb, hh, 0, 0)

    in_specs = [
        pl.BlockSpec((None, hb, c, d), q_map),
        pl.BlockSpec((None, hb, ps, d), kv_map),
        pl.BlockSpec((None, hb, ps, d), kv_map),
    ]
    args = [q, pool["k"], pool["v"]]
    if quant:
        in_specs += [pl.BlockSpec((None, hb, ps), sc_map),
                     pl.BlockSpec((None, hb, ps), sc_map)]
        args += [pool["k_scale"], pool["v_scale"]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb, npg),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, hb, c, d), q_map),
        scratch_shapes=[pltpu.VMEM((hb, c), jnp.float32),
                        pltpu.VMEM((hb, c), jnp.float32),
                        pltpu.VMEM((hb, c, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c, d), q.dtype),
        compiler_params=compiler_params(
            interpret, ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, start, *args)


def paged_pool_attention(q, pool, page_table, q_pos, sm_scale=None,
                         head_block=8, mesh=None, interpret=None):
    """Decode/chunk attention DIRECTLY against a paged K/V pool.

    ``q``: (B, H, C, D) queries — C contiguous chunk positions per row
    (decode C == 1, chunked prefill / speculative verify C > 1).
    ``pool``: one layer's pool dict — ``{"k", "v"}`` planes of
    (num_pages, H, page_size, D), plus ``{"k_scale", "v_scale"}``
    (num_pages, H, page_size) f32 when the pool is int8.
    ``page_table``: (B, P) int32, ``num_pages`` = the "no page"
    sentinel. ``q_pos``: (B, C) traced absolute positions with the
    chunk contract ``q_pos[b, c] == q_pos[b, 0] + c`` — every caller
    (``_paged_chunk``'s ``start + j``, the decode step's ``pos``)
    satisfies it, and it lets the positions ride the scalar-prefetch
    channel as one int per row.

    Output matches ``paged_attention(q, paged_gather(...), ...)`` up to
    online-softmax summation order — token-identical at temperature 0.

    ``mesh``: None, or ``(Mesh, tp_axis_name)`` for head-sharded pools
    (PR 15 layout): the kernel is head-local, so it runs under
    ``shard_map`` with zero collectives.
    """
    if q.ndim != 4:
        raise ValueError("paged_pool_attention expects (B, H, C, D)")
    if interpret is None:
        interpret = use_interpret()
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = d ** -0.5
    page_table = jnp.asarray(page_table, jnp.int32)
    start = jnp.asarray(q_pos, jnp.int32)[:, 0]
    call = functools.partial(_call_kernel, sm_scale=sm_scale,
                             head_block=head_block, interpret=interpret)
    if mesh is None:
        return call(q, pool, page_table, start)
    m, axis = mesh
    kv = P(None, axis, None, None)
    pool_spec = {k: (kv if pool[k].ndim == 4 else P(None, axis, None))
                 for k in pool}
    return jax.shard_map(call, mesh=m,
                         in_specs=(kv, pool_spec, P(None, None), P(None)),
                         out_specs=kv, check_vma=False)(
        q, pool, page_table, start)
