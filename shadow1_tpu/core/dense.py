"""Dense per-host update/select primitives — the no-scatter toolkit.

XLA lowers a scatter with dynamic per-row indices (``arr.at[h, col].set``)
to a serialized loop on TPU: measured 4.3 ms for a [4096, 32] single-slot
write and 371 ms for a 131k-element batch scatter — the entire per-window
cost of round 2's engine. Every hot-path "write one slot per host" in this
package therefore goes through these helpers, which express the update as a
one-hot mask + ``where`` (dense, fuses into one cheap elementwise kernel)
instead of a scatter.

Reads of one slot per host go the same way: on the v5e XLA runs a
gather of one element per host one element at a time, 12–13.5 ns an
element, while ``extract_col(read_sel(col, C), arr)`` streams the plane once
at HBM speed and wins while the slot axis is under ~2,500 tall (configs
reach 1,024). The rule: reads and writes of one slot per host are both
one-hot passes; ``get_col`` is that read for a single plane, and callers
that read several planes at one column build ``read_sel`` once (PERF.md §6,
PR 26 and PR 33).

Layout contract (round-4 rewrite): the HOST axis is the LAST (minor/lane)
axis of every per-host state tensor, the slot/capacity axis is second-to-
last, and any field axes lead: ``[C, H]``, ``[S, H]``, ``[NP, C, H]``.
Rationale, measured on the target chip: TPU tiles the two minor axes
(8 sublanes × 128 lanes), so (a) per-host reductions over a slot axis must
run over the SUBLANE axis to vectorize across hosts (7× faster than the
lane-axis reduction the old host-major layout forced), and (b) a minor
axis of width NP=10 padded to 128 lanes inflated every payload tensor
12.8× in HBM. Host-minor keeps the wide, contiguous axis on the lanes.

These helpers also avoid ``argmin``/``cumsum`` along the slot axis — both
measured ~0.3–2.5 ms per call at [1000, 256] on the chip IN EITHER layout
(they lower to slow cross-lane/sublane sequences). ``first_true`` uses a
min-over-iota reduction instead.

The semantics are exactly those of ``arr.at[..., col, h].set(val)`` with an
out-of-range drop: hosts where ``mask`` is False (or ``col`` out of range)
are untouched.
"""

from __future__ import annotations

import jax.numpy as jnp


def onehot_col(col, cap: int, mask=None) -> jnp.ndarray:
    """bool [C, H]: True at (col[h], h) where mask[h] (and col in range)."""
    sel = jnp.arange(cap, dtype=col.dtype)[:, None] == col[None, :]
    if mask is not None:
        sel = sel & mask[None, :]
    return sel


def _expand(sel, ndim):
    return sel.reshape((1,) * (ndim - sel.ndim) + sel.shape)


def set_col(arr, col, val, mask=None):
    """Dense ``arr[..., col[h], h] = val[..., h] where mask[h]`` for
    [*L, C, H] arrays. ``val`` may be scalar or [H] (or [*L, H])."""
    sel = _expand(onehot_col(col, arr.shape[-2], mask), arr.ndim)
    val = jnp.asarray(val, arr.dtype)
    if val.ndim == 0:
        return jnp.where(sel, val, arr)
    # val [..., H] -> broadcast over the slot axis.
    return jnp.where(sel, jnp.expand_dims(val, -2), arr)


def add_col(arr, col, val, mask=None):
    """Dense ``arr[..., col[h], h] += val[..., h] where mask[h]``."""
    sel = _expand(onehot_col(col, arr.shape[-2], mask), arr.ndim)
    val = jnp.asarray(val, arr.dtype)
    if val.ndim >= 1:
        val = jnp.expand_dims(val, -2)
    return arr + jnp.where(sel, val, jnp.zeros((), arr.dtype))


def read_sel(col, cap: int) -> jnp.ndarray:
    """The read one-hot, bool [C, H]: True at (clip(col[h]), h).
    ``extract_col(read_sel(col, C), arr)`` is ``get_col(arr, col)``; build
    it once per column vector and hand it to ``extract_col`` for every plane
    read at that column."""
    return onehot_col(jnp.clip(col, 0, cap - 1), cap)


def get_col(arr, col):
    """Read ``arr[..., col[h], h]`` → [*L, H] (col clipped into range): one
    one-hot pass over the plane, not a gather."""
    return extract_col(read_sel(col, arr.shape[-2]), arr)


def extract_col(sel, arr):
    """Value at the one-hot True of ``sel`` per host: [*L, C, H] → [*L, H].

    ``sel`` [C, H] must be at most one-hot per host (the pop-min and
    message-boundary invariants — see core/events.py); hosts with no True
    read 0 (False). Masked reduction over the sublane axis: ``any`` for
    bool, else a sum in the array's own dtype (jnp.sum would promote
    i32 → i64 and silently break the u32 wrapping-arithmetic contract)."""
    s = _expand(sel, arr.ndim)
    if arr.dtype == jnp.bool_:
        return (arr & s).any(axis=-2)
    return jnp.where(s, arr, 0).sum(axis=-2, dtype=arr.dtype)


def table_rows(table, row):
    """``table[row[h], j]`` of a 64-bit ``[R, W]`` table → ``(lo, hi)``, its
    two u32 half words as ``[W, H]`` planes: every host's row of the table,
    without an index per host.

    The one-hot read again, over the table's row axis, as a product: the
    table cut into its eight byte planes (bf16 holds a byte exactly), times
    ``onehot(row)``, accumulated in f32 — each sum has one non-zero term, a
    byte, so the read is exact on any backend (``rng._log_tbl_read`` is the
    precedent). The MXU does R · W · H multiply-adds a byte plane, which at
    a few hundred rows is tens of µs where the same one-hot as compares and
    selects is R · W · H VPU operations a half word (PERF.md §6, PR 51). A
    traced table (a fleet lane's) is cut at run time, [R, W] operations; a
    closed-over one folds."""
    n_rows, width = table.shape
    t = table.astype(jnp.uint64)
    planes = jnp.stack([
        ((t >> jnp.uint64(8 * i)) & jnp.uint64(0xFF)).astype(jnp.bfloat16).T
        for i in range(8)]).reshape(8 * width, n_rows)      # rows (byte, j)
    b = jnp.dot(
        planes, onehot_col(row, n_rows).astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    ).astype(jnp.uint32).reshape(8, width, -1)
    return (b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24),
            b[4] | (b[5] << 8) | (b[6] << 16) | (b[7] << 24))


def pick_row(planes, idx):
    """``plane[idx[c, h], h]`` for each ``[W, H]`` plane of ``planes`` and an
    ``idx`` ``[C, H]`` → a ``[C, H]`` plane each (0 where ``idx`` is outside
    the plane): ``get_col`` with a column of indices per host. One masked
    sum per plane over the W axis, in the plane's own dtype (at most one
    non-zero term: the plane's own integer);
    XLA:TPU makes ONE fusion of the planes' sums and their shared compare,
    and stores nothing ``[W, C, H]`` wide — a compare shared through a
    stacked plane axis it stores (PERF.md §6, PR 51)."""
    sel = idx[None] == jnp.arange(planes[0].shape[0], dtype=idx.dtype)[:, None, None]
    return tuple(jnp.where(sel, p[:, None, :], 0).sum(axis=0, dtype=p.dtype)
                 for p in planes)


def first_true(m) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-host first True of a bool [C, H]: (any[H], onehot [C, H]).

    min-over-iota reduction, not cumsum (see module docstring)."""
    cap = m.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)[:, None]
    any_, first = first_true_idx(m)
    # first is 0 where ~any_, so gate the one-hot on the mask.
    return any_, (iota == first[None, :]) & any_[None, :]


def last_true(m) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-host HIGHEST True of a bool [C, H]: (any[H], index[H]).

    Index is 0 where no True (callers gate on the any-mask)."""
    cap = m.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)[:, None]
    last = jnp.max(jnp.where(m, iota, -1), axis=0)
    return m.any(axis=0), jnp.maximum(last, 0).astype(jnp.int32)


def first_true_idx(m) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-host first-True INDEX of a bool [C, H]: (any[H], index[H]).

    Index is 0 where no True (callers gate on the any-mask). The reduction
    replacement for ``argmax(m, axis=slot)`` on bool masks."""
    cap = m.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)[:, None]
    first = jnp.min(jnp.where(m, iota, cap), axis=0)
    return m.any(axis=0), jnp.where(first < cap, first, 0).astype(jnp.int32)


def payload(n_hosts: int, *rows) -> jnp.ndarray:
    """Build an [NP, H] i32 payload from per-plane [H] rows (None = zeros).

    ``p.at[i].set(row)`` chains trace to NP scatter primitives per packet
    construction — ~850 scatter eqns in the rung-3 program before XLA
    simplification. Stacking builds the same tensor as one concatenate."""
    from shadow1_tpu.consts import NP

    if len(rows) > NP:
        raise ValueError(f"payload(): {len(rows)} rows > NP={NP} planes")
    zeros = None
    out = []
    for i in range(NP):
        r = rows[i] if i < len(rows) else None
        if r is None:
            if zeros is None:
                zeros = jnp.zeros(n_hosts, jnp.int32)
            r = zeros
        else:
            r = jnp.broadcast_to(jnp.asarray(r, jnp.int32), (n_hosts,))
        out.append(r)
    return jnp.stack(out)
