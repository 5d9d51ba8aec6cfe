"""Bit-exact capacity migration of the engine's SoA planes.

Runs on HOST (plain numpy) at chunk boundaries or checkpoint load — never
inside the jitted window path — and re-shapes the ``[C, H]`` event-buffer
planes / ``[P, H]`` outbox planes to a new static capacity. Every operation
addresses the slot axis as ``axis=-2``, so planes with leading axes migrate
identically: a fleet state's ``[E, C, H]`` planes (fleet transactional
retry / fleet ``--auto-caps``) go through the exact same code path as a
solo ``[C, H]`` state — per lane, the migration is the solo migration:

* **grow**: append free-slot sentinel rows (exactly the ``evbuf_init`` /
  ``outbox_init`` fill values), occupied slots untouched;
* **shrink**: stable-compact each host column's OCCUPIED slots to the front,
  then truncate. Raises if any host holds more events than the new cap —
  the controller only shrinks to ladder steps above the measured high-water,
  so a refusal means the caller's policy is broken, not the data.

Exactness argument: pop order is decided purely by the (time, tb) keys
(core/events.py module docstring) and free-slot CONTENT is never read
(every reader masks on ``kind != K_NONE`` / ``slot < cnt``), so any
permutation of a column's occupied slots plus any free-slot padding is
semantically the identity. Slot ASSIGNMENT of future pushes differs after a
migration (first-free search, delivery rank), but that is an engine-internal
layout detail with no observable effect — the same argument that makes
``deliver_batch``'s layout engine-internal. The one caveat is overflow:
WHICH events drop when a buffer fills is layout-defined, so runs are
bit-exact across migrations only while the overflow counters stay 0 —
the same contract cross-engine parity already lives under
(docs/SEMANTICS.md "Bounds and overflow").
"""

from __future__ import annotations

import numpy as np

from shadow1_tpu.consts import K_NONE

_I64_MAX = np.int64(np.iinfo(np.int64).max)
_I32_FREE = np.int32(np.iinfo(np.int32).max)  # events.I32_FREE


def _tb_split_np(v) -> tuple[np.int32, np.int32]:
    """numpy mirror of core/events.tb_split (order-preserving i64 → i32×2)."""
    hi = np.int32(int(v) >> 32)
    lo_bits = (int(v) & 0xFFFFFFFF) ^ 0x80000000  # sign-flip, as uint bits
    lo = np.int32(lo_bits - (1 << 32) if lo_bits >= (1 << 31) else lo_bits)
    return hi, lo


def _pad_rows(x: np.ndarray, n: int, fill) -> np.ndarray:
    """Append ``n`` slot rows (axis -2) filled with ``fill``."""
    pad_shape = x.shape[:-2] + (n, x.shape[-1])
    return np.concatenate([x, np.full(pad_shape, fill, x.dtype)], axis=-2)


def _expand_order(order: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Broadcast a slot-permutation ``order`` (shape [*lead, C, H]) onto a
    plane ``x`` (shape [*lead, *extra, C, H]) whose extra axes (e.g. the
    payload NP axis) sit between the shared leading axes and the slot axis:
    insert singleton axes there, then broadcast."""
    k = x.ndim - order.ndim
    o = order.reshape(order.shape[:-2] + (1,) * k + order.shape[-2:])
    return np.broadcast_to(o, x.shape)


def resize_evbuf(buf, new_cap: int):
    """EventBuf (numpy leaves) at cap C → the same queue contents at
    ``new_cap``. Returns a new EventBuf; [H]-vector/scalar leaves
    (self_ctr, epoch, n_elig, u32) are capacity-independent and carried
    as-is. Leading axes ([E, C, H] fleet planes) migrate per lane."""
    kind = np.asarray(buf.kind)
    cap = kind.shape[-2]
    new_cap = int(new_cap)
    if new_cap == cap:
        return buf
    planes = {f: np.asarray(getattr(buf, f))
              for f in ("time_hi", "time_lo", "t32", "tb_hi", "tb_lo",
                        "kind", "p")}
    if new_cap < cap:
        occupied = planes["kind"] != K_NONE
        n_occ = occupied.sum(axis=-2).max()
        if n_occ > new_cap:
            raise ValueError(
                f"cannot shrink ev_cap {cap} -> {new_cap}: a host holds "
                f"{int(n_occ)} events"
            )
        # Stable partition: occupied slots first, original slot order kept
        # (argsort of the free flag is stable ⇒ ties keep slot order).
        order = np.argsort(~occupied, axis=-2, kind="stable")
        for f, x in planes.items():
            o = _expand_order(order, x)
            planes[f] = np.take_along_axis(x, o, axis=-2)[..., :new_cap, :]
    else:
        thi, tlo = _tb_split_np(_I64_MAX)
        n = new_cap - cap
        planes["time_hi"] = _pad_rows(planes["time_hi"], n, thi)
        planes["time_lo"] = _pad_rows(planes["time_lo"], n, tlo)
        planes["t32"] = _pad_rows(planes["t32"], n, _I32_FREE)
        for f in ("tb_hi", "tb_lo", "p"):
            planes[f] = _pad_rows(planes[f], n, 0)
        planes["kind"] = _pad_rows(planes["kind"], n, K_NONE)
    return buf._replace(**planes)


def resize_outbox(ob, new_cap: int):
    """Outbox (numpy leaves) at cap P → ``new_cap``. Outbox entries are
    contiguous in [0, cnt) per host (append-only within a window, cleared at
    window end — chunk boundaries always see cnt == 0), so grow pads rows
    and shrink truncates; slots ≥ cnt are never read, so stale content
    beyond the truncation point is immaterial."""
    dst = np.asarray(ob.dst)
    cap = dst.shape[-2]
    new_cap = int(new_cap)
    if new_cap == cap:
        return ob
    if new_cap < cap and int(np.asarray(ob.cnt).max()) > new_cap:
        raise ValueError(
            f"cannot shrink outbox_cap {cap} -> {new_cap}: a host has "
            f"{int(np.asarray(ob.cnt).max())} pending sends"
        )
    planes = {}
    for f in ("dst", "kind", "depart_hi", "depart_lo", "ctr", "p"):
        x = np.asarray(getattr(ob, f))
        planes[f] = (x[..., :new_cap, :] if new_cap < cap
                     else _pad_rows(x, new_cap - cap, 0))
    return ob._replace(**planes)


_MQ_FREE = {"mq_sock": -1, "mq_end": 0, "mq_meta": 0}  # tcp.tcp_init's fill


def mq_pool_of(st) -> int | None:
    """The message-boundary pool's slots a host in ``st`` (tcp/tcp.py), or
    None where the model has no TCP."""
    tcp = getattr(st.model, "tcp", None)
    return None if tcp is None else np.asarray(tcp["mq_sock"]).shape[-2]


def resize_mq_pool(tcp: dict, new_cap: int) -> dict:
    """The TCP dict (numpy leaves) with its boundary pool ``[P, H]`` at
    ``new_cap`` slots. A socket's queue is the SET of slots that name it
    (no use reads a slot's position), so a grow appends free slots and a
    shrink moves each host's occupied slots to the front, order kept, and
    truncates; it refuses where a host holds more than the new pool."""
    sock = np.asarray(tcp["mq_sock"])
    cap, new_cap = sock.shape[-2], int(new_cap)
    if new_cap == cap:
        return tcp
    planes = {f: np.asarray(tcp[f]) for f in _MQ_FREE}
    if new_cap < cap:
        occupied = sock >= 0
        n_occ = int(occupied.sum(axis=-2).max())
        if n_occ > new_cap:
            raise ValueError(
                f"cannot shrink msgq_pool {cap} -> {new_cap}: a host holds "
                f"{n_occ} message boundaries"
            )
        order = np.argsort(~occupied, axis=-2, kind="stable")
        planes = {f: np.take_along_axis(x, order, axis=-2)[..., :new_cap, :]
                  for f, x in planes.items()}
    else:
        planes = {f: _pad_rows(x, new_cap - cap, _MQ_FREE[f])
                  for f, x in planes.items()}
    return {**tcp, **planes}


def resize_state(st, ev_cap: int | None = None, outbox_cap: int | None = None,
                 msgq_pool: int | None = None):
    """SimState → SimState with the event buffer / outbox / message-boundary
    pool migrated. Leaves come back as numpy; callers re-place on device
    (engine.place_state). Metrics, the rest of the model state, cpu_busy and
    the telemetry ring are capacity-independent and pass through untouched."""
    repl = {}
    if ev_cap is not None and int(ev_cap) != st.evbuf.kind.shape[-2]:
        repl["evbuf"] = resize_evbuf(st.evbuf, ev_cap)
    if outbox_cap is not None and int(outbox_cap) != st.outbox.dst.shape[-2]:
        repl["outbox"] = resize_outbox(st.outbox, outbox_cap)
    if msgq_pool is not None and mq_pool_of(st) not in (None, int(msgq_pool)):
        repl["model"] = st.model._replace(
            tcp=resize_mq_pool(st.model.tcp, msgq_pool))
    return st._replace(**repl) if repl else st
