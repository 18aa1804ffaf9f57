"""MXU group-by aggregation — one-hot matmul kernels.

XLA lowers ``scatter``-with-duplicate-indices poorly on TPU (measured
~15M rows/s for int64 scatter-add vs >150M rows/s for the MXU path on the
same shapes), so the hash-agg hot path (BASELINE.md config 4) computes
per-group COUNT/SUM via ``dot_general`` against a one-hot slot matrix:

- the group-id per row (slot index: key-base, NULL slot, scrap slot —
  mirror of ops/agg.hash_agg_tile's layout) selects a one-hot column;
- integer values are **byte-split** into int8 planes (biased to [-128,127])
  so the whole aggregation is exact int8×int8→int32 MXU work, widened to
  int64 between blocks: sum(v) = Σ_k 2^(8k)·S_k + count·BIAS_OFFSET;
- real values ride a separate f32 matmul, accumulated in f64 across
  blocks (the callers' ``lax.scan`` steps: int32 partials cannot
  overflow while a block stays ≤ 2^23 rows).

Plane layout: plane 0 is always the row mask (→ present + count_star);
each aggregate appends its own validity plane and value planes.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def named_program(fn, klass: str):
    """``fn`` under the name of its flight-recorder compile class, for
    ``jax.jit``: the XLA module is then ``jit_<klass>`` (a profile's
    'XLA Modules' line tells selection from TopN, and a module maps to
    a ``compile_class`` by eye) and its ops trace under
    ``jax.named_scope(klass)``."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        with jax.named_scope(klass):
            return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = klass
    return program


def int_planes_needed(vmin: int, vmax: int) -> int:
    """Bytes needed to represent [vmin, vmax] biased to unsigned."""
    for nb in (1, 2, 3, 4, 8):
        lo, hi = -(1 << (8 * nb - 1)), (1 << (8 * nb - 1)) - 1
        if lo <= vmin and vmax <= hi:
            return min(nb, 8)
    return 8


def bias_offset(nb: int) -> int:
    """sum(v) correction: v = Σ(c_k+128)·2^(8k) − 2^(8nb−1)."""
    return 128 * sum(1 << (8 * k) for k in range(nb)) - (1 << (8 * nb - 1))


@dataclass(frozen=True)
class PlaneLayout:
    """Static description of one spec's planes in the stacked matrices.

    ``ok_plane``: index of the validity int8 plane (None → use plane 0).
    ``byte_planes``: int8 plane indices of the value bytes (LSB first).
    ``f32_plane``: index into the f32 matrix for real sums.
    ``nb``: byte count for the int value split.
    """

    kind: str
    ok_plane: Optional[int] = None
    byte_planes: tuple = ()
    f32_plane: Optional[int] = None
    nb: int = 0


def build_layouts(specs, arg_is_real: Sequence[bool],
                  arg_nbytes: Sequence[int],
                  arg_ok_is_mask: Optional[Sequence[bool]] = None):
    """→ (layouts, n_int8_planes, n_f32_planes). Plane 0 = row mask.

    ``arg_ok_is_mask[i]`` — the arg's validity is provably identical to
    the row mask (bare NOT NULL column ref), so its validity plane aliases
    plane 0 instead of shipping a duplicate through the matmul.
    """
    if arg_ok_is_mask is None:
        arg_ok_is_mask = [False] * len(specs)
    layouts = []
    p8 = 1
    pf = 0
    for spec, is_real, nb, ok_is_mask in zip(specs, arg_is_real, arg_nbytes,
                                             arg_ok_is_mask):
        if spec.kind == "count_star":
            layouts.append(PlaneLayout("count_star"))
            continue
        if ok_is_mask:
            okp = 0
        else:
            okp = p8
            p8 += 1
        if spec.kind == "count":
            layouts.append(PlaneLayout("count", ok_plane=okp))
        elif spec.kind in ("sum", "avg"):
            if is_real:
                layouts.append(PlaneLayout(spec.kind, ok_plane=okp,
                                           f32_plane=pf))
                pf += 1
            else:
                bp = tuple(range(p8, p8 + nb))
                layouts.append(PlaneLayout(spec.kind, ok_plane=okp,
                                           byte_planes=bp, nb=nb))
                p8 += nb
        else:
            raise ValueError(f"matmul path cannot handle {spec.kind}")
    return layouts, p8, pf


def matmul_supported(specs) -> bool:
    return all(s.kind in ("count", "count_star", "sum", "avg") for s in specs)


def make_planes(layouts, specs, cols, mask):
    """Build the stacked int8 / f32 plane matrices for one row chunk.

    ``cols[i]``: (values, validity) for spec i (values int or f32).
    Returns (L8: (P8, n) int8, Lf: (Pf, n) f32 | None).
    """
    n = mask.shape[0]
    int8_planes = [mask.astype(jnp.int8)]
    f32_planes = []
    for lay, spec, col in zip(layouts, specs, cols):
        if lay.kind == "count_star":
            continue
        values, validity = col
        if lay.ok_plane == 0:       # validity aliases the row mask
            ok = mask
        else:
            ok = mask & validity
            int8_planes.append(ok.astype(jnp.int8))
        if lay.f32_plane is not None:
            f32_planes.append(
                jnp.where(ok, values, jnp.zeros_like(values))
                .astype(jnp.float32))
        elif lay.byte_planes:
            nb = lay.nb
            v64 = values.astype(jnp.int64) if nb > 4 else \
                values.astype(jnp.int32)
            # the bias is added UNSIGNED, where it wraps: at nb = 4 / 8
            # it is 2^31 / 2^63, which the signed width cannot hold (a
            # computed argument is as wide as its planes' dtype)
            udt = np.uint64 if nb > 4 else np.uint32
            biased = v64.astype(udt) + udt(1 << (8 * nb - 1))
            for k in range(nb):
                byte = ((biased >> (8 * k)) & 0xFF).astype(jnp.int32) - 128
                int8_planes.append(
                    jnp.where(ok, byte, jnp.zeros_like(byte))
                    .astype(jnp.int8))
    L8 = jnp.stack(int8_planes)
    Lf = jnp.stack(f32_planes) if f32_planes else None
    return L8, Lf


def twolevel_lo(p8: int, pf: int) -> Optional[int]:
    """Pick the low-radix width for the factorized group-by, or None.

    The two-level kernel packs every plane's LO lanes side by side into one
    matmul operand, so the widest plane set bounds LO: max(p8, pf)·LO ≤ 128
    keeps each stacked operand inside one MXU lane tile.
    """
    width = max(p8, max(pf, 1))
    lo = 128
    while lo > 4 and width * lo > 128:
        lo //= 2
    return lo if width * lo <= 128 else None


def twolevel_dims(slots: int, p8: int, pf: int) -> tuple:
    """→ (LO, HI) for the factorized kernel (see twolevel_partial)."""
    lo = twolevel_lo(p8, pf)
    assert lo is not None, (p8, pf)
    hi = -(-slots // lo)
    return lo, ((hi + 7) // 8) * 8


def twolevel_partial(idx, L8, Lf, LO: int, HI: int):
    """Factorized one-hot group-by over ONE row block: slot = hi·LO + lo.

    A straight one-hot matmul (this module's first body) materializes an
    (block, slots) one-hot operand — both its VPU generation cost and its
    MXU contraction width scale with ``slots`` (≈1152 lanes for 1k
    groups). Factorizing the slot id as hi·LO+lo turns the aggregation
    into

      S2[hi, p·LO+lo] = Σ_rows onehot_hi[row, hi]·(L_p[row]·onehot_lo[row, lo])

    — ONE dot_general with a (block, HI) int8 left operand and a
    (block, P·LO) right operand, so one-hot generation shrinks from
    ``slots`` to ``HI + P·LO`` lanes per row and the MXU width from
    ``slots`` to ≤128. Measured ~8× faster than the straight one-hot on
    v5e for 1k groups (2.2ms vs 19ms per 2^23-row chunk).

    Returns PACKED partials (S2_8 (HI, p8·LO) int32, S2_f (HI, pf·LO)
    float32 | None); accumulate them across blocks in wider dtypes and
    call twolevel_unpack once at the end. int32 packing is exact while the
    per-call block stays ≤ 2^23 rows (|int8| ≤ 127 ⇒ |cell| < 2^30).
    """
    block = idx.shape[0]
    p8 = L8.shape[0]
    hi_iota = lax.broadcasted_iota(jnp.int32, (block, HI), 1)
    lo_iota = lax.broadcasted_iota(jnp.int32, (block, LO), 1)
    i32 = idx.astype(jnp.int32)
    hi = i32 // LO
    lo = i32 - hi * LO
    A8 = (hi[:, None] == hi_iota).astype(jnp.int8)
    onehot_lo = lo[:, None] == lo_iota
    zero8 = jnp.zeros((block, LO), jnp.int8)
    W8 = jnp.concatenate(
        [jnp.where(onehot_lo, L8[p][:, None], zero8) for p in range(p8)],
        axis=1)
    S2_8 = lax.dot_general(A8, W8, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.int32)
    S2_f = None
    if Lf is not None:
        pf = Lf.shape[0]
        Af = A8.astype(jnp.float32)
        zerof = jnp.zeros((block, LO), jnp.float32)
        Wf = jnp.concatenate(
            [jnp.where(onehot_lo, Lf[p][:, None], zerof)
             for p in range(pf)], axis=1)
        S2_f = lax.dot_general(Af, Wf, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    return S2_8, S2_f


def twolevel_unpack(S2, n_planes: int, LO: int, slots: int, xp=jnp):
    """(HI, P·LO) packed partials → (P, slots) plane matrix."""
    HI = S2.shape[0]
    S = xp.transpose(S2.reshape(HI, n_planes, LO), (1, 0, 2)) \
        .reshape(n_planes, HI * LO)
    return S[:, :slots]


def states_from_matmul(layouts, specs, S8, Sf, xp=jnp):
    """Reassemble hash-agg state dicts (ops/agg.py layout) from the matmul
    partials.  Also returns the present mask (mask-plane count > 0).
    ``xp``: jnp in-kernel, or numpy for host-side finalize after a packed
    device→host transfer."""
    mask_count = S8[0]
    present = mask_count > 0
    states = []
    for lay, spec in zip(layouts, specs):
        if lay.kind == "count_star":
            states.append({"count": mask_count})
            continue
        okc = S8[lay.ok_plane]
        if lay.kind == "count":
            states.append({"count": okc})
        elif lay.f32_plane is not None:     # real sum/avg
            s = Sf[lay.f32_plane]
            states.append({"sum": s, "nonnull": okc} if lay.kind == "sum"
                          else {"sum": s, "count": okc})
        else:                               # int sum/avg
            total = xp.zeros_like(okc)
            for k, p in enumerate(lay.byte_planes):
                total = total + (S8[p] << (8 * k))
            total = total + okc * bias_offset(lay.nb)
            states.append({"sum": total, "nonnull": okc}
                          if lay.kind == "sum"
                          else {"sum": total, "count": okc})
    return present, states


def slot_index(key_pair, capacity: int, base, row_mask):
    """Row → slot id (group / NULL / scrap), mirroring
    ops/agg.hash_agg_tile's layout.  Returns (idx int32, overflow bool).

    For int32 keys the shift runs in int32 (int64 is pair-emulated on
    TPU): base is the host-computed key minimum, so every in-range key
    shifts into [0, capacity); a key far enough above base to wrap goes
    negative, fails the range check, and raises ``overflow`` — never a
    silent misclassification.
    """
    kv, km = key_pair
    null_slot = capacity
    scrap = capacity + 1
    if kv.dtype == jnp.int32:
        shifted = kv - base.astype(jnp.int32)
    else:
        shifted = kv.astype(jnp.int64) - base
    in_range = (shifted >= 0) & (shifted < capacity)
    idx = jnp.where(km & in_range, shifted, 0).astype(jnp.int32)
    idx = jnp.where(km, jnp.where(in_range, idx, scrap), null_slot)
    idx = jnp.where(row_mask, idx, scrap)
    overflow = jnp.any(row_mask & km & ~in_range)
    return idx, overflow
