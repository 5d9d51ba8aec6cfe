"""Pallas fused pop-min kernel — the event-buffer pop in ONE memory pass.

The XLA pop (core/events.py pop_until) lowers to ~12 full-plane HBM passes
(eligibility, three masked mins with their broadcasts/compares, the one-hot
extraction, the clears); on-chip each [C, H] pass costs ~50-95 us at rung-3
shape and the composite measured ~1.35 ms/round (tools/roundprobe.py,
docs/PERF.md round-5). The whole computation is a per-lane (per-host)
reduction chain over the sublane (slot) axis with NO cross-lane traffic —
exactly the shape a fused VMEM kernel wants: read each plane once, keep
every intermediate in registers/VMEM, write the two updated planes and the
[H]-vector results once.

Semantics are IDENTICAL to events.pop_until(extract="sum") — same
lexicographic (t32, tb_hi, tb_lo) masked-min chain, same equality one-hot
(exact: the key triple is unique per host, events.py module docstring),
same masked-sum extraction — asserted bit-equal in tests/test_events.py
and selectable per-run via EngineParams.pop_impl = "pallas".

The kernels run GRIDLESS: one program instance, whole-array blocks, so
the full plane set (keys + NP payload planes) must fit the ~12 MB VMEM
budget; ``preflight`` checks this and the engine refuses an explicit
``pallas`` selection that cannot hold (tiling them over a grid is ROADMAP
Speed 4/7). The updated t32/kind planes alias their inputs (in-place
update, no spare HBM copy).

Reference anchor: this kernel is the batched analogue of the per-host
binary-heap pop in the reference's worker loop
(src/main/core/scheduler/scheduler.c runNextEvent path,
src/main/utility/priority-queue.c).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from shadow1_tpu.consts import K_NONE, NP
from shadow1_tpu.core import events as ev


# Plane counts per kernel call (inputs + aliased outputs resident in VMEM);
# shared by the per-call checks and the engine-facing preflight so the two
# cannot drift.
POP_PLANES = 6 + NP
PUSH_PLANES = 7 + NP
OBOX_PLANES = 5 + NP


def _check_vmem(cap: int, h: int, planes: int, knob: str = "ev_cap") -> None:
    """The kernels run GRIDLESS — one program instance, whole-array blocks —
    so the full plane set must fit VMEM; reject loudly instead of silently
    compiling an over-VMEM kernel."""
    need = 4 * planes * cap * h
    if need > 12 * 2**20:
        raise ValueError(
            f"{knob}={cap} x {h} hosts needs {need / 2**20:.1f} MB of VMEM "
            "for the gridless fused kernels; use pop_impl/push_impl='xla' "
            "for shapes this large"
        )


def preflight(ev_cap: int, outbox_cap: int, h: int,
              pop_pallas: bool, push_pallas: bool) -> None:
    """Raise ValueError if any SELECTED fused kernel cannot hold its plane
    set in VMEM at this shape. No-op off-TPU: every other backend runs the
    kernels in interpret mode (_resolve_interpret), which has no VMEM."""
    if jax.default_backend() != "tpu":
        return
    if pop_pallas:
        _check_vmem(ev_cap, h, planes=POP_PLANES)
    if push_pallas:
        _check_vmem(ev_cap, h, planes=PUSH_PLANES)
        _check_vmem(outbox_cap, h, planes=OBOX_PLANES, knob="outbox_cap")


# Mosaic cannot lower i64, and under x64 a Python int scalar crossing a jit
# boundary (jnp.where's) commits as i64 — as does jnp.sum's default integer
# accumulator. Every scalar constant inside the kernels is therefore an
# explicit jnp.int32 (built INSIDE the kernel body: Pallas rejects captured
# array constants) and every sum pins dtype=int32; a stray i64 here makes
# Mosaic's i64->i32 convert rule recurse to a RecursionError at lowering.


def _consts32():
    return (jnp.int32(ev.I32_FREE), jnp.int32(ev.I32_MAX),
            jnp.int32(K_NONE), jnp.int32(0))


def _pop_kernel(until_ref, t32_ref, hi_ref, lo_ref, kind_ref, p_ref,
                t32o_ref, kindo_ref, mt_ref, mhi_ref, mlo_ref, ko_ref,
                po_ref):
    _I32_FREE, _I32_MAX, _K_NONE32, _ZERO32 = _consts32()
    u = until_ref[0]
    t = t32_ref[:, :]                                   # [C, BH] i32
    k = kind_ref[:, :]
    elig = (k != _K_NONE32) & (t < u)
    tm = jnp.where(elig, t, _I32_FREE)
    mint = tm.min(axis=0, keepdims=True)                # [1, BH]
    tie = elig & (tm == mint)
    him = jnp.where(tie, hi_ref[:, :], _I32_MAX)
    minhi = him.min(axis=0, keepdims=True)
    tie2 = tie & (him == minhi)
    lom = jnp.where(tie2, lo_ref[:, :], _I32_MAX)
    minlo = lom.min(axis=0, keepdims=True)
    sel = tie2 & (lom == minlo)                         # one-hot per host
    t32o_ref[:, :] = jnp.where(sel, _I32_FREE, t)
    kindo_ref[:, :] = jnp.where(sel, _K_NONE32, k)
    mt_ref[:, :] = mint
    mhi_ref[:, :] = minhi
    mlo_ref[:, :] = minlo
    ko_ref[:, :] = jnp.where(sel, k, _ZERO32).sum(axis=0, keepdims=True,
                                                  dtype=jnp.int32)
    po_ref[:, :, :] = jnp.where(sel[None], p_ref[:, :, :], _ZERO32).sum(
        axis=1, keepdims=True, dtype=jnp.int32
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pop_call(t32, tb_hi, tb_lo, kind, p, u32, *, interpret=False):
    cap, h = kind.shape
    if not interpret:
        _check_vmem(cap, h, planes=POP_PLANES)
    blk2 = pl.BlockSpec((cap, h), lambda: (0, 0))
    vec = pl.BlockSpec((1, h), lambda: (0, 0))
    out_shapes = (
        jax.ShapeDtypeStruct((cap, h), jnp.int32),   # t32'
        jax.ShapeDtypeStruct((cap, h), jnp.int32),   # kind'
        jax.ShapeDtypeStruct((1, h), jnp.int32),     # min_t
        jax.ShapeDtypeStruct((1, h), jnp.int32),     # min_hi
        jax.ShapeDtypeStruct((1, h), jnp.int32),     # min_lo
        jax.ShapeDtypeStruct((1, h), jnp.int32),     # kind_out
        jax.ShapeDtypeStruct((NP, 1, h), jnp.int32),  # p_out
    )
    return pl.pallas_call(
        _pop_kernel,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # until32 (1,)
            blk2, blk2, blk2, blk2,
            pl.BlockSpec((NP, cap, h), lambda: (0, 0, 0)),
        ],
        out_specs=(
            blk2, blk2, vec, vec, vec, vec,
            pl.BlockSpec((NP, 1, h), lambda: (0, 0, 0)),
        ),
        out_shape=out_shapes,
        input_output_aliases={1: 0, 4: 1},           # t32, kind in-place
        interpret=interpret,
    )(jnp.asarray(u32).reshape(1), t32, tb_hi, tb_lo, kind, p)


def _resolve_interpret(interpret):
    """Mosaic compiles only for TPU; every other backend (the CPU test
    platform, virtual device meshes) runs the kernels in interpret mode.
    Resolved here so call sites cannot forget the incantation."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def pop_until_fused(buf: ev.EventBuf, until, *,
                    interpret: bool | None = None) -> tuple[ev.EventBuf, ev.Popped]:
    """Drop-in fused replacement for events.pop_until (extract="sum")."""
    interpret = _resolve_interpret(interpret)
    u32 = ev.until32(buf, until)
    t32o, kindo, mt, mhi, mlo, ko, po = _pop_call(
        buf.t32, buf.tb_hi, buf.tb_lo, buf.kind, buf.p, u32,
        interpret=interpret,
    )
    mt, mhi, mlo, ko = mt[0], mhi[0], mlo[0], ko[0]
    mask = mt < u32
    popped = ev.Popped(
        mask=mask,
        time=jnp.where(mask, buf.epoch + mt.astype(jnp.int64), 0),
        kind=ko,
        p=po[:, 0, :],
        tb=jnp.where(mask, ev.tb_join(mhi, mlo), 0),
    )
    buf = buf._replace(
        t32=t32o, kind=kindo,
        n_elig=buf.n_elig - mask.astype(jnp.int32),
    )
    return buf, popped


def _push_kernel(maskv_ref, thi_v, tlo_v, t32_v, bhi_v, blo_v, kind_v, p_v,
                 thi_ref, tlo_ref, t32_ref, bhi_ref, blo_ref, kind_ref, p_ref,
                 thi_o, tlo_o, t32_o, bhi_o, blo_o, kind_o, p_o, over_o):
    _I32_FREE, _I32_MAX, _K_NONE32, _ZERO32 = _consts32()
    k = kind_ref[:, :]                                  # [C, BH]
    free = k == _K_NONE32
    idx = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)
    cap = jnp.int32(k.shape[0])
    fidx = jnp.where(free, idx, cap).min(axis=0, keepdims=True)  # [1, BH]
    has = fidx < cap
    mv = maskv_ref[:, :] != _ZERO32
    ok = mv & has
    w = free & (idx == fidx) & ok
    thi_o[:, :] = jnp.where(w, thi_v[:, :], thi_ref[:, :])
    tlo_o[:, :] = jnp.where(w, tlo_v[:, :], tlo_ref[:, :])
    t32_o[:, :] = jnp.where(w, t32_v[:, :], t32_ref[:, :])
    bhi_o[:, :] = jnp.where(w, bhi_v[:, :], bhi_ref[:, :])
    blo_o[:, :] = jnp.where(w, blo_v[:, :], blo_ref[:, :])
    kind_o[:, :] = jnp.where(w, kind_v[:, :], k)
    p_o[:, :, :] = jnp.where(w[None], p_v[:, :, :], p_ref[:, :, :])
    over_o[:, :] = (mv & ~has).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _push_call(maskv, thi_v, tlo_v, t32_v, bhi_v, blo_v, kind_v, p_v,
               thi, tlo, t32, bhi, blo, kind, p, *, interpret=False):
    cap, h = kind.shape
    if not interpret:
        _check_vmem(cap, h, planes=PUSH_PLANES)
    blk2 = pl.BlockSpec((cap, h), lambda: (0, 0))
    vec = pl.BlockSpec((1, h), lambda: (0, 0))
    pvec = pl.BlockSpec((NP, 1, h), lambda: (0, 0, 0))
    pblk = pl.BlockSpec((NP, cap, h), lambda: (0, 0, 0))
    plane = jax.ShapeDtypeStruct((cap, h), jnp.int32)
    out_shapes = (
        plane, plane, plane, plane, plane, plane,
        jax.ShapeDtypeStruct((NP, cap, h), jnp.int32),
        jax.ShapeDtypeStruct((1, h), jnp.int32),     # overflow
    )
    return pl.pallas_call(
        _push_kernel,
        in_specs=[vec, vec, vec, vec, vec, vec, vec, pvec,
                  blk2, blk2, blk2, blk2, blk2, blk2, pblk],
        out_specs=(blk2, blk2, blk2, blk2, blk2, blk2, pblk, vec),
        out_shape=out_shapes,
        # The seven buffer planes update in place.
        input_output_aliases={8: 0, 9: 1, 10: 2, 11: 3, 12: 4, 13: 5, 14: 6},
        interpret=interpret,
    )(maskv, thi_v, tlo_v, t32_v, bhi_v, blo_v, kind_v, p_v,
      thi, tlo, t32, bhi, blo, kind, p)


def _push_fused(buf: ev.EventBuf, mask, time, tb, kind, p, *,
                advance_ctr: bool, interpret: bool | None = None):
    """Shared body of the fused push_local/push_back (tb = self_ctr or the
    original tie-break, per events.py semantics)."""
    interpret = _resolve_interpret(interpret)
    time = jnp.asarray(time, jnp.int64)
    thi_v, tlo_v = ev.tb_split(time)
    bhi_v, blo_v = ev.tb_split(jnp.asarray(tb, jnp.int64))
    t32_v = ev._t32_of(time, buf.epoch)
    row = lambda x: jnp.asarray(x, jnp.int32).reshape(1, -1)
    thi, tlo, t32, bhi, blo, kindo, po, over = _push_call(
        row(mask), row(thi_v), row(tlo_v), row(t32_v), row(bhi_v),
        row(blo_v), row(jnp.broadcast_to(jnp.asarray(kind, jnp.int32),
                                         time.shape)),
        jnp.asarray(p, jnp.int32)[:, None, :],
        buf.time_hi, buf.time_lo, buf.t32, buf.tb_hi, buf.tb_lo, buf.kind,
        buf.p, interpret=interpret,
    )
    over = (over[0] != 0) & mask
    ok = mask & ~over
    buf = buf._replace(
        time_hi=thi, time_lo=tlo, t32=t32, tb_hi=bhi, tb_lo=blo,
        kind=kindo, p=po,
        n_elig=buf.n_elig + (ok & (t32_v < buf.u32)).astype(jnp.int32),
    )
    if advance_ctr:
        buf = buf._replace(self_ctr=buf.self_ctr + ok.astype(jnp.int64))
    return buf, over


def push_local_fused(buf: ev.EventBuf, mask, time, kind, p, *,
                     interpret: bool | None = None):
    """Drop-in fused replacement for events.push_local."""
    return _push_fused(buf, mask, time, buf.self_ctr, kind, p,
                       advance_ctr=True, interpret=interpret)


def push_back_fused(buf: ev.EventBuf, mask, time, tb, kind, p, *,
                    interpret: bool | None = None):
    """Drop-in fused replacement for events.push_back."""
    return _push_fused(buf, mask, time, tb, kind, p,
                       advance_ctr=False, interpret=interpret)


def _obox_kernel(cnt_ref, okv_ref, dst_v, kind_v, dhi_v, dlo_v, ctr_v, p_v,
                 dst_ref, kind_ref, dhi_ref, dlo_ref, ctr_ref, p_ref,
                 dst_o, kind_o, dhi_o, dlo_o, ctr_o, p_o):
    _ZERO32 = _consts32()[3]
    cap = dst_ref.shape[0]
    idx = jax.lax.broadcasted_iota(jnp.int32, (cap,) + cnt_ref.shape[1:], 0)
    w = (idx == cnt_ref[:, :]) & (okv_ref[:, :] != _ZERO32)
    dst_o[:, :] = jnp.where(w, dst_v[:, :], dst_ref[:, :])
    kind_o[:, :] = jnp.where(w, kind_v[:, :], kind_ref[:, :])
    dhi_o[:, :] = jnp.where(w, dhi_v[:, :], dhi_ref[:, :])
    dlo_o[:, :] = jnp.where(w, dlo_v[:, :], dlo_ref[:, :])
    ctr_o[:, :] = jnp.where(w, ctr_v[:, :], ctr_ref[:, :])
    p_o[:, :, :] = jnp.where(w[None], p_v[:, :, :], p_ref[:, :, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _obox_call(cnt, okv, dst_v, kind_v, dhi_v, dlo_v, ctr_v, p_v,
               dst, kind, dhi, dlo, ctr, p, *, interpret=False):
    cap, h = dst.shape
    if not interpret:
        _check_vmem(cap, h, planes=OBOX_PLANES, knob="outbox_cap")
    blk2 = pl.BlockSpec((cap, h), lambda: (0, 0))
    vec = pl.BlockSpec((1, h), lambda: (0, 0))
    pvec = pl.BlockSpec((NP, 1, h), lambda: (0, 0, 0))
    pblk = pl.BlockSpec((NP, cap, h), lambda: (0, 0, 0))
    plane = jax.ShapeDtypeStruct((cap, h), jnp.int32)
    return pl.pallas_call(
        _obox_kernel,
        in_specs=[vec, vec, vec, vec, vec, vec, vec, pvec,
                  blk2, blk2, blk2, blk2, blk2, pblk],
        out_specs=(blk2, blk2, blk2, blk2, blk2, pblk),
        out_shape=(plane, plane, plane, plane, plane,
                   jax.ShapeDtypeStruct((NP, cap, h), jnp.int32)),
        input_output_aliases={8: 0, 9: 1, 10: 2, 11: 3, 12: 4, 13: 5},
        interpret=interpret,
    )(cnt, okv, dst_v, kind_v, dhi_v, dlo_v, ctr_v, p_v,
      dst, kind, dhi, dlo, ctr, p)


def outbox_append_fused(ob, mask, dst, kind, depart, p, *,
                        interpret: bool | None = None):
    """Drop-in fused replacement for outbox.outbox_append: the write slot is
    ``cnt[h]`` (not a first-free search), so the kernel is a pure one-hot
    write pass over the [P, H] planes."""
    interpret = _resolve_interpret(interpret)
    cap = ob.dst.shape[0]
    ok = mask & (ob.cnt < cap)
    dhi_v, dlo_v = ev.tb_split(jnp.asarray(depart, jnp.int64))
    row = lambda x: jnp.asarray(x, jnp.int32).reshape(1, -1)
    h = ob.cnt.shape[0]
    dsto, kindo, dhio, dloo, ctro, po = _obox_call(
        row(ob.cnt), row(ok), row(jnp.broadcast_to(jnp.asarray(dst, jnp.int32), (h,))),
        row(jnp.broadcast_to(jnp.asarray(kind, jnp.int32), (h,))),
        row(dhi_v), row(dlo_v), row(ob.pkt_ctr.astype(jnp.int32)),
        jnp.asarray(p, jnp.int32)[:, None, :],
        ob.dst, ob.kind, ob.depart_hi, ob.depart_lo, ob.ctr, ob.p,
        interpret=interpret,
    )
    ob = ob._replace(
        dst=dsto, kind=kindo, depart_hi=dhio, depart_lo=dloo, ctr=ctro, p=po,
        cnt=ob.cnt + ok.astype(jnp.int32),
        pkt_ctr=ob.pkt_ctr + ok.astype(jnp.int64),
    )
    return ob, ok
