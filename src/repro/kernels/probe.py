"""Pallas TPU kernels: batched probing of hash tables in VMEM.

TPU adaptation of the paper's hot paths.  On CPUs the per-op cost at load
factor alpha is pointer chasing; on TPU the equivalent hot loop is the probe
sequence, and the roofline term is HBM traffic.

**Where the kernels run.**  ``pallas_call`` below is the only launcher: it
reads the JAX platform when the op is traced.  The TPU compiles every kernel
with Mosaic; the CPU emulates the same kernels (tests, CI); any other
platform raises.  No caller chooses.

**Linear probing (the default backend): per-query windows.**  ops.py
gathers every query's probe window (``kernels/window.py``: the rows of the
table, viewed as ``[C/128, 128]``, that hold ``h0 .. h0+max_probes-1``),
and these kernels evaluate the window math of ``window.py`` over dense
``[WQT, W]`` tiles — the same functions the jnp op set runs as plain XLA.
The window covers the whole probe bound, so they are exact and never
partial.  Four kernels:

* ``_probe_kernel``   — single-table lookup; emits the hit's window lane
  (-1 = absent).  ops.py turns the lane into the slot, reads the value with
  one element gather, and the delete path tombstones that slot.
* ``_probe2_kernel``  — the fused **rebuild-epoch** probe: old-table window,
  dense hazard-buffer compare, new-table window in ONE pass (the paper's
  Lemma-4.1 order old -> hazard -> new is applied by ops.py to the three
  outputs, which serve both the ordered lookup and the ordered delete).
* ``_claim_kernel``   — the insert's claim pass: presence and the first
  non-LIVE lane of the window (``window.insert`` settles claims on one
  slot and lets the losers claim again).
* ``_extract_kernel`` — the rebuild chunk scan: LIVE mask of the chunk and
  each live entry's rank among them (prefix counts as two small triangular
  matmuls), which ops.py uses to compact the hazard entries with one
  scatter.

**twochoice / cuckoo / chain: sorted-slab kernels.**  These backends keep
the older skeleton: ops.py sorts the batch, a scalar-prefetch BlockSpec
picks the two table blocks covering each ``QT``-query tile's slab, and the
probe loop gathers per-query values from that VMEM window.  Queries whose
window escapes the slab fall back to the jnp oracle.  Mosaic refuses their
per-query 1-D gathers, so they run on the CPU only; on a TPU ``dhash.make``
refuses these backends with ``fused=True`` (``backend.BucketBackend.
tpu_refusal``).

VMEM budget (v5e, 16 MiB scoped): a probe2 tile holds four ``[WQT, W]`` i32
windows (256 KiB each at ``max_probes <= 129``, double-buffered 2 MiB), the
hazard buffer as ``[1, CH]`` rows (128 KiB each at ``CH = 4096``) and
``[WQT, HZB]`` compare intermediates (512 KiB each).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import window
from repro.kernels.window import LANES

I32 = jnp.int32
EMPTY, LIVE = 0, 1

WQT = 256     # queries per tile of the windowed kernels
HZB = 512     # hazard entries compared per inner step of the probe2 kernel
QT = 1024     # queries per tile of the sorted-slab kernels
SLAB = 4096   # table words per block (2 consecutive blocks resident); also
              # the largest rebuild chunk a fused table takes

# Resident new-table blocks per query tile in the sorted-slab rebuild-epoch
# probes (the second level of the two-level tile map).  16 block pairs cover
# a new table of up to ~16 SLABs (64K slots) COMPLETELY; beyond that, the
# least-populated blocks of a tile overflow to the gated jnp fallback.  This
# is the DEFAULT of the ``nres_cap`` parameter the rebuild-epoch ops accept;
# the per-backend value lives on the ``BucketBackend`` descriptor
# (core/backend.py) and is threaded through ``dhash.make()``.
NRES_CAP = 16

# Dirty-tail window of the arena-sorted chain backend: nodes inserted since
# the last compaction live in a contiguous tail, resolved by a dense window
# compare (the hazard-buffer treatment).  A tail grown past DIRTY_CAP is no
# longer fully visible to the window, so the fused chain ops escape to the
# pointer-chasing jnp reference — ``backend.chain_maybe_compact`` re-sorts
# the arena at exactly this threshold to keep the steady state on-kernel.
# Like NRES_CAP this is only the DEFAULT of the ``dirty_cap`` parameter;
# the live value is a ``BucketBackend`` descriptor field.
DIRTY_CAP = 512


def platform() -> str:
    """The platform the kernels are traced for: ``"tpu"`` or ``"cpu"``."""
    p = jax.default_backend()
    if p not in ("tpu", "cpu"):
        raise ValueError(f"no Pallas kernel path for platform {p!r}; the "
                         f"kernels compile for TPU and run interpreted on CPU")
    return p


def pallas_call(kernel, **kw):
    """``pl.pallas_call`` compiled by Mosaic on a TPU and run by the Pallas
    interpreter on the CPU — resolved from the platform at trace time."""
    return pl.pallas_call(kernel, interpret=platform() == "cpu", **kw)


def _probe_kernel(off_ref, qk_ref, kw_ref, sw_ref, hit_ref, *,
                  max_probes: int):
    hit_ref[...] = window.probe(kw_ref[...], sw_ref[...], off_ref[...],
                                qk_ref[...], max_probes)[0]


def _claim_kernel(off_ref, qk_ref, kw_ref, sw_ref, hit_ref, free_ref, *,
                  max_probes: int):
    """Insert claim pass: the presence probe plus the first non-LIVE lane of
    the window (``window.claim``)."""
    hit_ref[...], free_ref[...] = window.claim(
        kw_ref[...], sw_ref[...], off_ref[...], qk_ref[...], max_probes)


def _probe2_kernel(offo_ref, offn_ref, qk_ref, kwo_ref, swo_ref, kwn_ref,
                   swn_ref, hk_ref, hl_ref, hito_ref, hz_ref, hitn_ref, *,
                   max_probes: int):
    """Fused rebuild-epoch probe: the old-table window, the hazard buffer
    (dense compare, ``HZB`` entries per step, first live match), and the
    new-table window — three outputs, one pass."""
    qk = qk_ref[...]
    hito_ref[...] = window.probe(kwo_ref[...], swo_ref[...], offo_ref[...],
                                 qk, max_probes)[0]
    hitn_ref[...] = window.probe(kwn_ref[...], swn_ref[...], offn_ref[...],
                                 qk, max_probes)[0]
    ch = hk_ref.shape[1]
    hb = min(ch, HZB)

    def hazard(c, best):
        start = pl.multiple_of(c * hb, hb)
        k = hk_ref[:, pl.ds(start, hb)]
        live = hl_ref[:, pl.ds(start, hb)]
        idx = start + jax.lax.broadcasted_iota(I32, (1, hb), 1)
        return jnp.minimum(best,
                           window.first((qk == k) & (live != 0), idx, ch))

    best = jax.lax.fori_loop(0, ch // hb, hazard,
                             jnp.full(qk.shape, ch, I32))
    hz_ref[...] = jnp.where(best < ch, best, -1)


def _query_specs(w: int):
    col = pl.BlockSpec((WQT, 1), lambda i: (i, 0))
    win = pl.BlockSpec((WQT, w), lambda i: (i, 0))
    return col, win


def probe_windows(off, qk, kw, sw, *, max_probes: int):
    """Single-table probe over gathered windows.  ``off``/``qk`` [Q, 1],
    ``kw``/``sw`` [Q, W] with Q a ``WQT`` multiple.  Returns hit lanes
    [Q, 1] (-1 = absent)."""
    q, w = kw.shape
    col, win = _query_specs(w)
    return pallas_call(
        functools.partial(_probe_kernel, max_probes=max_probes),
        grid=(q // WQT,), in_specs=[col, col, win, win], out_specs=col,
        out_shape=jax.ShapeDtypeStruct((q, 1), I32))(off, qk, kw, sw)


def claim_windows(off, qk, kw, sw, *, max_probes: int):
    """Insert claim pass over gathered windows.  Returns (hit, free) lanes,
    each [Q, 1] (-1 = none)."""
    q, w = kw.shape
    col, win = _query_specs(w)
    return pallas_call(
        functools.partial(_claim_kernel, max_probes=max_probes),
        grid=(q // WQT,), in_specs=[col, col, win, win],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((q, 1), I32)] * 2)(off, qk, kw, sw)


def probe2_windows(off_old, off_new, qk, old_w, new_w, hazard_key,
                   hazard_live_i32, *, max_probes: int):
    """Fused rebuild-epoch probe.  ``old_w``/``new_w`` are (key, state)
    window pairs [Q, W]; the hazard arrays are [1, CH] with CH a multiple of
    ``min(CH, HZB)``.  Returns (old hit lane, hazard index, new hit lane),
    each [Q, 1] (-1 = none)."""
    q, w = old_w[0].shape
    ch = hazard_key.shape[1]
    col, win = _query_specs(w)
    hspec = pl.BlockSpec((1, ch), lambda i: (0, 0))
    return pallas_call(
        functools.partial(_probe2_kernel, max_probes=max_probes),
        grid=(q // WQT,),
        in_specs=[col, col, col, win, win, win, win, hspec, hspec],
        out_specs=[col, col, col],
        out_shape=[jax.ShapeDtypeStruct((q, 1), I32)] * 3)(
        off_old, off_new, qk, *old_w, *new_w, hazard_key, hazard_live_i32)


# ---------------------------------------------------------------------------
# rebuild chunk extraction: LIVE mask + compaction ranks
# ---------------------------------------------------------------------------

def _extract_kernel(lim_ref, st_ref, live_ref, rank_ref):
    """Rebuild chunk scan over the chunk's states [R, 128] (slot ``r*128+l``
    of the chunk).  ``lim`` is the number of chunk slots inside the table.
    Emits the LIVE mask and each live slot's rank among the live slots in
    scan order: within-row inclusive prefix counts (``x @ U``, U upper
    triangular) plus the counts of the rows above (``L @ row totals``, L
    strictly lower triangular).  Operands are 0/1 and row totals <= 128, so
    the matmuls are exact in any MXU precision."""
    r = st_ref.shape[0]
    slot = (jax.lax.broadcasted_iota(I32, (r, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(I32, (r, LANES), 1))
    live = (slot < lim_ref[0, 0]) & (st_ref[...] == LIVE)
    x = live.astype(jnp.float32)
    upper = (jax.lax.broadcasted_iota(I32, (LANES, LANES), 0)
             <= jax.lax.broadcasted_iota(I32, (LANES, LANES), 1))
    incl = jnp.dot(x, upper.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    below = (jax.lax.broadcasted_iota(I32, (r, r), 1)
             < jax.lax.broadcasted_iota(I32, (r, r), 0))
    tot = jnp.broadcast_to(incl[:, LANES - 1:], (r, LANES))
    above = jnp.dot(below.astype(jnp.float32), tot,
                    preferred_element_type=jnp.float32)
    live_ref[...] = live.astype(I32)
    rank_ref[...] = (incl + above).astype(I32) - 1


def extract_rows(states: jax.Array, lim: jax.Array):
    """Run the extract kernel on a chunk's states laid out [R, 128].
    ``lim``: scalar count of chunk slots below the table's capacity.
    Returns (live_i32 [R, 128], rank [R, 128])."""
    r = states.shape[0]
    blk = pl.BlockSpec((r, LANES), lambda: (0, 0))
    return pallas_call(
        _extract_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct((r, LANES), I32)] * 2)(
        jnp.reshape(lim, (1, 1)).astype(I32), states)


# ---------------------------------------------------------------------------
# twochoice: W-wide two-row gather kernels over row slabs
# ---------------------------------------------------------------------------
#
# The twochoice table is [B, W]; a query touches exactly rows ha(k), hb(k).
# The fused path expands each query into TWO row-entries (entry e < Q is the
# a-row of query e, entry e >= Q the b-row of query e - Q), applies the SAME
# sort + scalar-prefetch slab treatment keyed on the row index — ONE argsort
# + ONE pallas_call cover both choices — and recombines per query after the
# unsort.  Row blocks are [SLAB_R, W] with SLAB_R * W = SLAB words, so the
# VMEM budget matches the linear kernels.

def _tc_rowslab(width: int) -> int:
    return max(SLAB // max(width, 1), 8)


def _tc_row_probe(base_blk, row, qk, k0, k1, v0, v1, s0, s1, width: int):
    """Shared W-wide row probe over one 2-row-block VMEM window.

    Returns (found, val, loc, complete); ``loc`` is the flat TABLE slot
    index row*W + lane of the LIVE hit (-1 when absent or the row escaped
    the resident window)."""
    slab_r = _tc_rowslab(width)
    off = row - base_blk * slab_r
    keys = jnp.concatenate([k0[...], k1[...]], axis=0)     # [2*SLAB_R, W]
    vals = jnp.concatenate([v0[...], v1[...]], axis=0)
    stat = jnp.concatenate([s0[...], s1[...]], axis=0)

    complete = (off >= 0) & (off < 2 * slab_r)
    safe = jnp.clip(off, 0, 2 * slab_r - 1)
    krow = jnp.take(keys, safe, axis=0)                    # [QT, W]
    vrow = jnp.take(vals, safe, axis=0)
    srow = jnp.take(stat, safe, axis=0)

    hit = (krow == qk[:, None]) & (srow == LIVE)
    found = hit.any(-1) & complete
    lane = jnp.argmax(hit, axis=-1)
    val = jnp.take_along_axis(vrow, lane[:, None], axis=-1)[:, 0]
    return (found, jnp.where(found, val, 0),
            jnp.where(found, row * width + lane.astype(I32), -1), complete)


def _tc_lookup_kernel(slab_ref,            # scalar-prefetch: [tiles]
                      row_ref, qk_ref,     # [QT] row index / key per entry
                      tk0, tk1, tv0, tv1, ts0, ts1,   # [SLAB_R, W] blocks
                      found_ref, val_ref, loc_ref, complete_ref,
                      *, width: int):
    """W-wide two-row gather lookup: each entry reads its single resident
    row, compares all W lanes at once, and emits (found, val, loc) with
    ``loc`` the flat slot index row*W + lane (-1 if absent)."""
    i = pl.program_id(0)
    found, val, loc, complete = _tc_row_probe(
        slab_ref[i], row_ref[...], qk_ref[...],
        tk0, tk1, tv0, tv1, ts0, ts1, width)
    found_ref[...] = found
    val_ref[...] = val
    loc_ref[...] = loc
    complete_ref[...] = complete


def _tc_insert_kernel(slab_ref,            # scalar-prefetch: [tiles]
                      row_ref, qk_ref, qm_ref,           # [QT] (qm: i32)
                      tk0, tk1, ts0, ts1,                # [SLAB_R, W] blocks
                      present_ref, claim_ref, complete_ref,
                      *, width: int):
    """Claim-a-lane batched twochoice insert.  Each entry (one row choice of
    one query) re-proves absence against its row, then joins a local claim
    loop on a VMEM copy of the resident row states: per round an entry picks
    its row's lowest non-LIVE lane, the lowest in-tile entry index wins a
    contested lane, and winners flip the lane LIVE locally.  Emits flat slot
    claims (row*W + lane in TABLE coordinates; -1 = none); ops.py drops
    shadowed b-claims, resolves cross-tile collisions, and routes conflicts
    to the jnp fallback — exact, never wrong, occasionally partial."""
    i = pl.program_id(0)
    slab_r = _tc_rowslab(width)
    base = slab_ref[i] * slab_r
    off = row_ref[...] - base
    qk = qk_ref[...]
    qm = qm_ref[...] != 0
    keys = jnp.concatenate([tk0[...], tk1[...]], axis=0)
    stat = jnp.concatenate([ts0[...], ts1[...]], axis=0)

    complete = (off >= 0) & (off < 2 * slab_r)
    safe = jnp.clip(off, 0, 2 * slab_r - 1)
    krow = jnp.take(keys, safe, axis=0)
    srow = jnp.take(stat, safe, axis=0)
    present = ((krow == qk[:, None]) & (srow == LIVE)).any(-1) & complete

    qn = off.shape[0]
    qidx = jax.lax.broadcasted_iota(I32, (qn,), 0)
    nloc = 2 * slab_r * width
    pending0 = qm & complete & ~present

    def claim_round(r, carry):
        st, pending, claim = carry
        srow_now = jnp.take(st, safe, axis=0)              # [QT, W]
        free = srow_now != LIVE
        has = pending & free.any(-1)
        lane = jnp.argmax(free, axis=-1)
        flat = safe * width + lane.astype(I32)             # local coords
        tgt = jnp.where(has, flat, nloc)
        cl = jnp.full((nloc,), qn, I32).at[tgt].min(qidx, mode="drop")
        won = has & (jnp.take(cl, flat, axis=0) == qidx)
        st = st.reshape(-1).at[jnp.where(won, flat, nloc)].set(
            LIVE, mode="drop").reshape(2 * slab_r, width)
        claim = jnp.where(won, flat, claim)
        return st, pending & ~won, claim

    _, _, claim_loc = jax.lax.fori_loop(
        0, width, claim_round,
        (stat, pending0, jnp.full((qn,), -1, I32)))

    present_ref[...] = present
    claim_ref[...] = jnp.where(claim_loc >= 0,
                               base * width + claim_loc, -1)
    complete_ref[...] = complete


def _tc_specs(width: int):
    """Entry-tile and row-block BlockSpecs shared by the twochoice kernels."""
    slab_r = _tc_rowslab(width)
    qspec = pl.BlockSpec((QT,), lambda i, s: (i,))
    blk0 = pl.BlockSpec((slab_r, width), lambda i, s: (s[i], 0))
    blk1 = pl.BlockSpec((slab_r, width), lambda i, s: (s[i] + 1, 0))
    return qspec, blk0, blk1


def tc_lookup_tiles(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                    row_sorted: jax.Array, qk_sorted: jax.Array,
                    slab_base: jax.Array):
    """Run the twochoice lookup kernel over pre-sorted, pre-tiled entries.

    tkey/tval/tstate: [Bpad, W] row-padded tables (Bpad a SLAB_R multiple
    plus one spare block, pad rows EMPTY).  row_sorted/qk_sorted: [E] entry
    rows/keys sorted by row, E a multiple of QT.  slab_base: [E/QT] row-block
    index.  Returns (found[E], val[E], loc[E], complete[E]).
    """
    e = row_sorted.shape[0]
    width = tkey.shape[1]
    slab_r = _tc_rowslab(width)
    assert e % QT == 0 and tkey.shape[0] % slab_r == 0
    tiles = e // QT
    qspec, blk0, blk1 = _tc_specs(width)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles,),
        in_specs=[qspec, qspec, blk0, blk1, blk0, blk1, blk0, blk1],
        out_specs=[qspec] * 4,
    )
    out_shape = [
        jax.ShapeDtypeStruct((e,), jnp.bool_),
        jax.ShapeDtypeStruct((e,), I32),
        jax.ShapeDtypeStruct((e,), I32),
        jax.ShapeDtypeStruct((e,), jnp.bool_),
    ]
    kernel = functools.partial(_tc_lookup_kernel, width=width)
    return pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape)(
        slab_base, row_sorted, qk_sorted,
        tkey, tkey, tval, tval, tstate, tstate)


def tc_insert_tiles(tkey: jax.Array, tstate: jax.Array,
                    row_sorted: jax.Array, qk_sorted: jax.Array,
                    qm_sorted_i32: jax.Array, slab_base: jax.Array):
    """Claim pass of the twochoice insert over pre-sorted, pre-tiled entries.

    Returns (present[E], claim[E] flat table slot or -1, complete[E]).
    """
    e = row_sorted.shape[0]
    width = tkey.shape[1]
    slab_r = _tc_rowslab(width)
    assert e % QT == 0 and tkey.shape[0] % slab_r == 0
    tiles = e // QT
    qspec, blk0, blk1 = _tc_specs(width)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles,),
        in_specs=[qspec, qspec, qspec, blk0, blk1, blk0, blk1],
        out_specs=[qspec] * 3,
    )
    out_shape = [
        jax.ShapeDtypeStruct((e,), jnp.bool_),
        jax.ShapeDtypeStruct((e,), I32),
        jax.ShapeDtypeStruct((e,), jnp.bool_),
    ]
    kernel = functools.partial(_tc_insert_kernel, width=width)
    return pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape)(
        slab_base, row_sorted, qk_sorted, qm_sorted_i32,
        tkey, tkey, tstate, tstate)


# ---------------------------------------------------------------------------
# twochoice rebuild-epoch probe2: old row + hazard + new row in ONE pass
# ---------------------------------------------------------------------------

def _tc_probe2_kernel(slab2_ref,           # scalar-prefetch: [1 + nres, tiles]
                      orow_ref, nrow_ref, qk_ref,        # [QT] per entry
                      ok0, ok1, ov0, ov1, os0, os1,      # old row blocks
                      nk0, nk1, nv0, nv1, ns0, ns1,      # new resident blocks
                      hk_ref, hv_ref, hl_ref,            # [CH] hazard buffer
                      fold_ref, vold_ref, lold_ref, cold_ref, hzidx_ref,
                      fnew_ref, vnew_ref, lnew_ref, cnew_ref,
                      *, width: int):
    """Fused twochoice rebuild-epoch probe: per entry (one row choice of one
    query) the OLD row gather, the dense hazard compare, and the NEW row
    gather land in a single pass — the same ``(tiles, nres)`` reduction grid
    as ``_probe2_kernel`` (row 0 of ``slab2`` anchors the sorted old
    row-blocks; rows 1.. are the tile's resident new row-blocks, and
    iterations ``r > 0`` merge further new windows into the revisited
    outputs).  The kernel emits per-entry COMPONENTS (old hit/val/flat
    slot/coverage, hazard index, new hit/val/flat slot/coverage); ops.py
    recombines the two entries of each query with a-row priority and applies
    the Lemma-4.1 ordering — so the same outputs serve both the ordered
    lookup and the ordered delete."""
    i = pl.program_id(0)
    r = pl.program_id(1)
    qk = qk_ref[...]
    f_n, v_n, l_n, c_n = _tc_row_probe(
        slab2_ref[1 + r, i], nrow_ref[...], qk,
        nk0, nk1, nv0, nv1, ns0, ns1, width)

    @pl.when(r == 0)
    def _init():
        f_o, v_o, l_o, c_o = _tc_row_probe(
            slab2_ref[0, i], orow_ref[...], qk,
            ok0, ok1, ov0, ov1, os0, os1, width)
        eq = (qk[:, None] == hk_ref[...][None, :]) & (hl_ref[...][None, :] != 0)
        f_hz = eq.any(-1)
        hz_i = jnp.argmax(eq, axis=-1)
        fold_ref[...] = f_o
        vold_ref[...] = v_o
        lold_ref[...] = l_o
        cold_ref[...] = c_o
        hzidx_ref[...] = jnp.where(f_hz, hz_i.astype(I32), -1)
        fnew_ref[...] = f_n
        vnew_ref[...] = v_n
        lnew_ref[...] = l_n
        cnew_ref[...] = c_n

    @pl.when(r > 0)
    def _merge():
        seen = fnew_ref[...]
        fnew_ref[...] = seen | f_n
        vnew_ref[...] = jnp.where(f_n & ~seen, v_n, vnew_ref[...])
        lnew_ref[...] = jnp.maximum(lnew_ref[...], l_n)
        cnew_ref[...] = cnew_ref[...] | c_n


def tc_probe2_tiles(old_padded, new_padded,
                    hazard_key: jax.Array, hazard_val: jax.Array,
                    hazard_live_i32: jax.Array,
                    orow_sorted: jax.Array, nrow_sorted: jax.Array,
                    qk_sorted: jax.Array, slab2: jax.Array):
    """Run the twochoice rebuild-epoch kernel over pre-sorted entries.

    old_padded/new_padded: (key, val, state) triples of row-padded [Bpad, W]
    tables (pad rows EMPTY; widths must match).  orow_sorted/nrow_sorted/
    qk_sorted: [E] entry old-rows / new-rows / keys sorted by OLD row, E a
    QT multiple.  slab2: [1 + nres, tiles] row-block map (row 0 old, rows
    1.. resident new blocks).

    Returns (f_old, v_old, loc_old, c_old, hz_idx, f_new, v_new, loc_new,
    c_new) per entry; locations are flat table slots (-1 = none).
    """
    e = orow_sorted.shape[0]
    (okk, ovv, oss), (nkk, nvv, nss) = old_padded, new_padded
    width = okk.shape[1]
    assert nkk.shape[1] == width, "old/new twochoice widths must match"
    slab_r = _tc_rowslab(width)
    assert e % QT == 0 and okk.shape[0] % slab_r == 0 and \
        nkk.shape[0] % slab_r == 0
    tiles = e // QT
    nres = slab2.shape[0] - 1
    assert nres >= 1
    ch = hazard_key.shape[0]

    qspec = pl.BlockSpec((QT,), lambda i, r, s: (i,))
    oblk0 = pl.BlockSpec((slab_r, width), lambda i, r, s: (s[0, i], 0))
    oblk1 = pl.BlockSpec((slab_r, width), lambda i, r, s: (s[0, i] + 1, 0))
    nblk0 = pl.BlockSpec((slab_r, width), lambda i, r, s: (s[1 + r, i], 0))
    nblk1 = pl.BlockSpec((slab_r, width), lambda i, r, s: (s[1 + r, i] + 1, 0))
    hspec = pl.BlockSpec((ch,), lambda i, r, s: (0,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles, nres),
        in_specs=[qspec, qspec, qspec,
                  oblk0, oblk1, oblk0, oblk1, oblk0, oblk1,
                  nblk0, nblk1, nblk0, nblk1, nblk0, nblk1,
                  hspec, hspec, hspec],
        out_specs=[qspec] * 9,
    )
    out_shape = [
        jax.ShapeDtypeStruct((e,), jnp.bool_),    # f_old
        jax.ShapeDtypeStruct((e,), I32),          # v_old
        jax.ShapeDtypeStruct((e,), I32),          # loc_old (flat slot)
        jax.ShapeDtypeStruct((e,), jnp.bool_),    # c_old
        jax.ShapeDtypeStruct((e,), I32),          # hazard index (-1 = none)
        jax.ShapeDtypeStruct((e,), jnp.bool_),    # f_new
        jax.ShapeDtypeStruct((e,), I32),          # v_new
        jax.ShapeDtypeStruct((e,), I32),          # loc_new (flat slot)
        jax.ShapeDtypeStruct((e,), jnp.bool_),    # c_new
    ]
    kernel = functools.partial(_tc_probe2_kernel, width=width)
    return pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape)(
        slab2, orow_sorted, nrow_sorted, qk_sorted,
        okk, okk, ovv, ovv, oss, oss,
        nkk, nkk, nvv, nvv, nss, nss,
        hazard_key, hazard_val, hazard_live_i32)


# ---------------------------------------------------------------------------
# chain: segment-window probes over the arena-sorted node layout
# ---------------------------------------------------------------------------
#
# The chain arena, once compacted by ops.chain_compact_fused, is bucket-
# sorted: bucket b's nodes occupy the contiguous segment
# [bstart[b], bstart[b] + blen[b]).  A chain probe is then the SAME slab-
# window reduction as a linear probe — h0 = bstart[b] — except termination is
# the segment length (the packed arena has no EMPTY sentinels between
# segments), so the kernels take a per-query ``qlen`` bound instead of
# stopping at EMPTY.  Cross-segment reads cannot false-match: a key's bucket
# is a function of the key, so a LIVE node with a matching key in another
# bucket's segment is impossible.  Nodes inserted after the compaction (the
# dirty tail) are resolved OUTSIDE the kernel by a dense window compare in
# ops.py — the hazard-buffer treatment — and a tail grown past DIRTY_CAP
# escapes to the pointer-chasing jnp reference via the gated fallback.

def _chain_window_probe(base_blk, h0, qlen, qk, k0, k1, v0, v1, s0, s1,
                        max_probes: int):
    """Segment-bounded probe loop over one 2-block VMEM window.

    Like ``window.probe`` but the probe run is [h0, h0 + qlen) — absence is
    proven by exhausting the segment, not by an EMPTY sentinel.  ``complete``
    additionally requires ``qlen <= max_probes`` (a segment longer than the
    probe bound cannot prove absence).  Returns (found, val, loc, complete);
    ``loc`` is the padded-arena node coordinate of the LIVE hit, -1 if none.
    """
    base = base_blk * SLAB
    off = h0 - base
    keys = jnp.concatenate([k0[...], k1[...]])
    vals = jnp.concatenate([v0[...], v1[...]])
    stat = jnp.concatenate([s0[...], s1[...]])

    complete = ((off >= 0) & (off + max_probes <= 2 * SLAB)
                & (qlen <= max_probes))
    safe_off = jnp.clip(off, 0, 2 * SLAB - max_probes)

    def body(p, carry):
        found, val, loc = carry
        idx = safe_off + p
        k = jnp.take(keys, idx, axis=0)
        v = jnp.take(vals, idx, axis=0)
        s = jnp.take(stat, idx, axis=0)
        hit = (p < qlen) & ~found & (s == LIVE) & (k == qk)
        val = jnp.where(hit, v, val)
        loc = jnp.where(hit, base + idx, loc)
        return found | hit, val, loc

    q = h0.shape[0]
    init = (jnp.zeros((q,), bool), jnp.zeros((q,), I32),
            jnp.full((q,), -1, I32))
    found, val, loc = jax.lax.fori_loop(0, max_probes, body, init)
    return (found & complete, jnp.where(complete, val, 0),
            jnp.where(complete, loc, -1), complete)


def _chain_probe_kernel(slab_ref,            # scalar-prefetch: [tiles]
                        h0_ref, qlen_ref, qk_ref,        # [QT]
                        tk0, tk1, tv0, tv1, ts0, ts1,    # [SLAB] arena blocks
                        found_ref, val_ref, loc_ref, complete_ref,
                        *, max_probes: int):
    """Single-arena chain lookup over the sorted segments (steady state).
    Emits per-query node LOCATIONS alongside found/val so the fused chain
    delete tombstones with one scatter — same contract as ``_probe_kernel``.
    """
    i = pl.program_id(0)
    found, val, loc, complete = _chain_window_probe(
        slab_ref[i], h0_ref[...], qlen_ref[...], qk_ref[...],
        tk0, tk1, tv0, tv1, ts0, ts1, max_probes)
    found_ref[...] = found
    val_ref[...] = val
    loc_ref[...] = loc
    complete_ref[...] = complete


def _chain_probe2_kernel(slab2_ref,          # scalar-prefetch: [1+nres, tiles]
                         h0o_ref, qlo_ref, h0n_ref, qln_ref, qk_ref,  # [QT]
                         ok0, ok1, ov0, ov1, os0, os1,   # old arena blocks
                         nk0, nk1, nv0, nv1, ns0, ns1,   # new resident blocks
                         hk_ref, hv_ref, hl_ref,         # [CH] hazard buffer
                         fold_ref, vold_ref, lold_ref, cold_ref, hzidx_ref,
                         fnew_ref, vnew_ref, lnew_ref, cnew_ref,
                         *, max_probes: int):
    """Fused chain rebuild-epoch probe: the OLD segment probe, the dense
    hazard compare, and the NEW segment probe land in one pass on the same
    ``(tiles, nres)`` reduction grid as ``_probe2_kernel`` (row 0 of
    ``slab2`` anchors the old-arena slabs the shared sort produced; rows 1..
    are the tile's resident new-arena blocks, and iterations ``r > 0`` merge
    further new windows into the revisited outputs).  Emits per-query
    COMPONENTS — ops.py merges the dirty-tail windows of both arenas and
    applies the Lemma-4.1 ordering, so the same outputs serve both the
    ordered lookup and the ordered delete."""
    i = pl.program_id(0)
    r = pl.program_id(1)
    qk = qk_ref[...]
    f_n, v_n, l_n, c_n = _chain_window_probe(
        slab2_ref[1 + r, i], h0n_ref[...], qln_ref[...], qk,
        nk0, nk1, nv0, nv1, ns0, ns1, max_probes)

    @pl.when(r == 0)
    def _init():
        f_o, v_o, l_o, c_o = _chain_window_probe(
            slab2_ref[0, i], h0o_ref[...], qlo_ref[...], qk,
            ok0, ok1, ov0, ov1, os0, os1, max_probes)
        eq = (qk[:, None] == hk_ref[...][None, :]) & (hl_ref[...][None, :] != 0)
        f_hz = eq.any(-1)
        hz_i = jnp.argmax(eq, axis=-1)
        fold_ref[...] = f_o
        vold_ref[...] = v_o
        lold_ref[...] = l_o
        cold_ref[...] = c_o
        hzidx_ref[...] = jnp.where(f_hz, hz_i.astype(I32), -1)
        fnew_ref[...] = f_n
        vnew_ref[...] = v_n
        lnew_ref[...] = l_n
        cnew_ref[...] = c_n

    @pl.when(r > 0)
    def _merge():
        seen = fnew_ref[...]
        fnew_ref[...] = seen | f_n
        vnew_ref[...] = jnp.where(f_n & ~seen, v_n, vnew_ref[...])
        lnew_ref[...] = jnp.maximum(lnew_ref[...], l_n)
        cnew_ref[...] = cnew_ref[...] | c_n


def chain_probe_tiles(tkey: jax.Array, tval: jax.Array, tstate: jax.Array,
                      h0_sorted: jax.Array, qlen_sorted: jax.Array,
                      qk_sorted: jax.Array, slab_base: jax.Array, *,
                      max_probes: int):
    """Run the chain lookup kernel over pre-sorted, pre-tiled queries.

    tkey/tval/tstate: padded arena arrays (``ops._pad_table``-style).
    h0_sorted: per-query segment starts (``bstart[bucket]``), sorted
    ascending; qlen_sorted: matching segment lengths.  Returns
    (found[Q], val[Q], loc[Q], complete[Q]); ``loc`` is the padded-arena
    node coordinate (-1 if absent).
    """
    q = h0_sorted.shape[0]
    assert q % QT == 0 and tkey.shape[0] % SLAB == 0
    tiles = q // QT

    qspec = pl.BlockSpec((QT,), lambda i, s: (i,))
    blk0 = pl.BlockSpec((SLAB,), lambda i, s: (s[i],))
    blk1 = pl.BlockSpec((SLAB,), lambda i, s: (s[i] + 1,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles,),
        in_specs=[qspec, qspec, qspec,
                  blk0, blk1, blk0, blk1, blk0, blk1],
        out_specs=[qspec] * 4,
    )
    out_shape = [
        jax.ShapeDtypeStruct((q,), jnp.bool_),
        jax.ShapeDtypeStruct((q,), I32),
        jax.ShapeDtypeStruct((q,), I32),
        jax.ShapeDtypeStruct((q,), jnp.bool_),
    ]
    kernel = functools.partial(_chain_probe_kernel, max_probes=max_probes)
    return pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape)(
        slab_base, h0_sorted, qlen_sorted, qk_sorted,
        tkey, tkey, tval, tval, tstate, tstate)


def chain_probe2_tiles(old_padded, new_padded,
                       hazard_key: jax.Array, hazard_val: jax.Array,
                       hazard_live_i32: jax.Array,
                       h0o_sorted: jax.Array, qlo_sorted: jax.Array,
                       h0n_sorted: jax.Array, qln_sorted: jax.Array,
                       qk_sorted: jax.Array, slab2: jax.Array, *,
                       max_probes: int):
    """Run the chain rebuild-epoch kernel over pre-sorted queries.

    old_padded/new_padded: (key, val, state) arena triples padded
    independently.  h0o/qlo and h0n/qln: per-query segment (start, len) for
    the old and new arenas, sorted by the OLD start.  slab2:
    [1 + nres, tiles] block map (row 0 old, rows 1.. resident new blocks).

    Returns (f_old, v_old, loc_old, c_old, hz_idx, f_new, v_new, loc_new,
    c_new) per query; locations are padded-arena coordinates (-1 = none).
    """
    q = qk_sorted.shape[0]
    (okk, ovv, oss), (nkk, nvv, nss) = old_padded, new_padded
    assert q % QT == 0 and okk.shape[0] % SLAB == 0 and \
        nkk.shape[0] % SLAB == 0
    tiles = q // QT
    nres = slab2.shape[0] - 1
    assert nres >= 1
    ch = hazard_key.shape[0]

    qspec = pl.BlockSpec((QT,), lambda i, r, s: (i,))
    oblk0 = pl.BlockSpec((SLAB,), lambda i, r, s: (s[0, i],))
    oblk1 = pl.BlockSpec((SLAB,), lambda i, r, s: (s[0, i] + 1,))
    nblk0 = pl.BlockSpec((SLAB,), lambda i, r, s: (s[1 + r, i],))
    nblk1 = pl.BlockSpec((SLAB,), lambda i, r, s: (s[1 + r, i] + 1,))
    hspec = pl.BlockSpec((ch,), lambda i, r, s: (0,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(tiles, nres),
        in_specs=[qspec, qspec, qspec, qspec, qspec,
                  oblk0, oblk1, oblk0, oblk1, oblk0, oblk1,
                  nblk0, nblk1, nblk0, nblk1, nblk0, nblk1,
                  hspec, hspec, hspec],
        out_specs=[qspec] * 9,
    )
    out_shape = [
        jax.ShapeDtypeStruct((q,), jnp.bool_),    # f_old
        jax.ShapeDtypeStruct((q,), I32),          # v_old
        jax.ShapeDtypeStruct((q,), I32),          # loc_old (padded coords)
        jax.ShapeDtypeStruct((q,), jnp.bool_),    # c_old
        jax.ShapeDtypeStruct((q,), I32),          # hazard index (-1 = none)
        jax.ShapeDtypeStruct((q,), jnp.bool_),    # f_new
        jax.ShapeDtypeStruct((q,), I32),          # v_new
        jax.ShapeDtypeStruct((q,), I32),          # loc_new (padded coords)
        jax.ShapeDtypeStruct((q,), jnp.bool_),    # c_new
    ]
    kernel = functools.partial(_chain_probe2_kernel, max_probes=max_probes)
    return pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape)(
        slab2, h0o_sorted, qlo_sorted, h0n_sorted, qln_sorted, qk_sorted,
        okk, okk, ovv, ovv, oss, oss,
        nkk, nkk, nvv, nvv, nss, nss,
        hazard_key, hazard_val, hazard_live_i32)
