"""Preemption plane — signal-driven graceful drain.

Long runs end by SIGTERM far more often than by finishing: preemptible TPU
capacity delivers a termination notice with a deadline, not a clean exit.
Before this plane, a SIGTERM was indistinguishable from a crash — everything
since the last throttled snapshot was thrown away and the supervisor charged
a crash to its backoff accounting. Now the first SIGTERM/SIGINT *requests a
drain*: the one chunk runner (ckpt.run_chunked, which obs.run_with_heartbeat
and fleet.run.run_fleet both drive) finishes the in-flight chunk, commits it,
forces a final snapshot (obs.boundary_hook), and exits with the dedicated
:data:`consts.EXIT_PREEMPTED` code plus a parseable stdout record. The supervisor classifies that exit as
clean-resume — no backoff, no crash accounting, checkpoint kept — mirroring
the existing EXIT_CAPACITY taxonomy. Rerunning the same command resumes
bit-identically (the preemption contract, docs/SEMANTICS.md).

A second signal arriving ≥ :data:`FORCE_GRACE_S` after the first forces an
immediate default-action exit (the operator's "no really, die now"). The
grace window exists because one logical interrupt often arrives twice within
milliseconds — kernel process-group delivery plus the supervisor forwarding
to its child — and a duplicate must not turn a graceful drain into a kill.

jax-free: the supervisor imports this without initializing an accelerator.
"""

from __future__ import annotations

import os
import signal
import sys
import time

from shadow1_tpu.consts import EXIT_PREEMPTED  # noqa: F401  (re-export)

# Duplicate-delivery debounce: signals closer together than this are one
# logical drain request; later ones escalate to an immediate exit.
FORCE_GRACE_S = 1.0

_DRAIN_SIGNALS = (signal.SIGTERM, signal.SIGINT)


class PreemptedExit(Exception):
    """A drain request was honored: the in-flight chunk is committed (and
    checkpointed, when the run carries a checkpoint path) — the process
    should now exit :data:`EXIT_PREEMPTED`.

    Carries the committed state plus the progress the CLI's stdout record
    reports: ``signame`` (which signal asked), ``done_windows`` (committed
    this invocation), ``win_start`` (absolute sim clock — the resume
    point), ``ckpt`` (snapshot path, None when the run kept no checkpoint)
    and ``generation`` (lineage sequence number of the final snapshot)."""

    def __init__(self, st=None, signame: str = "SIGTERM",
                 done_windows: int = 0, win_start: int = 0,
                 ckpt: str | None = None, generation: int | None = None):
        self.st = st
        self.signame = signame
        self.done_windows = int(done_windows)
        self.win_start = int(win_start)
        self.ckpt = ckpt
        self.generation = generation
        super().__init__(
            f"drain complete after {signame}: {self.done_windows} window(s) "
            f"committed, sim_ns={self.win_start}"
            + (f", snapshot {ckpt}" if ckpt else ", no checkpoint path")
        )


def run_injection_hooks(sim_ns: int) -> None:
    """Chunk-boundary fault/preemption/hang injection, run by the one
    ``on_chunk`` (obs.boundary_hook) that the solo and the fleet runner hand
    ckpt.run_chunked, so the supervisor, drain and watchdog paths are
    testable in both shapes from ONE contract. Inert without the env vars:

    * ``SHADOW1_OBS_CRASH_PRE_SAVE_AT_NS`` — die before the checkpoint is
      written (the supervisor sees a zero-progress crash);
    * ``SHADOW1_OBS_SIGTERM_SELF_AT_NS`` — deliver SIGTERM to ourselves
      (the deterministic twin of a real preemption notice);
    * ``SHADOW1_OBS_HANG_AT_NS`` (+ ``SHADOW1_OBS_HANG_ONCE_FLAG``) — stop
      updating the progress sidecar while staying alive (the alive-but-stuck
      shape the watchdog must detect); the flag file makes it fire once so
      a respawn proceeds.

    The post-save crash hook (``SHADOW1_OBS_CRASH_AT_NS``) stays in
    obs.boundary_hook — it is gated on a save actually having happened."""
    crash_pre = os.environ.get("SHADOW1_OBS_CRASH_PRE_SAVE_AT_NS")
    if crash_pre is not None and sim_ns == int(crash_pre):
        os._exit(41)
    sigterm_at = os.environ.get("SHADOW1_OBS_SIGTERM_SELF_AT_NS")
    if sigterm_at is not None and sim_ns == int(sigterm_at):
        os.kill(os.getpid(), signal.SIGTERM)
    hang_at = os.environ.get("SHADOW1_OBS_HANG_AT_NS")
    if hang_at is not None and sim_ns == int(hang_at):
        flag = os.environ.get("SHADOW1_OBS_HANG_ONCE_FLAG")
        if flag is None or not os.path.exists(flag):
            if flag:
                with open(flag, "w") as f:
                    f.write("hung")
            while True:
                time.sleep(3600)


class DrainHandler:
    """Installable SIGTERM/SIGINT drain-request latch.

    ``requested`` flips on the first signal; the chunk runner polls it at
    chunk boundaries (never inside a window — a window is the atomic unit
    of the determinism contract). The handler only ever sets a flag: all
    actual drain work happens at the boundary, on the main thread, outside
    async dispatch."""

    def __init__(self, log=None):
        self.signame: str | None = None
        self._t_first: float | None = None
        self._log = log
        self._prev: dict[int, object] = {}

    @property
    def requested(self) -> bool:
        return self.signame is not None

    def _handle(self, signum, frame):
        now = time.monotonic()
        if self._t_first is not None and now - self._t_first >= FORCE_GRACE_S:
            # A genuine second request: restore the default action and
            # re-raise so the process dies with conventional 128+signum —
            # visible to the supervisor as a crash, not a drain.
            print(f"[preempt] second {signal.Signals(signum).name} — "
                  f"abandoning drain, exiting immediately",
                  file=sys.stderr, flush=True)
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)
            return
        if self._t_first is None:
            self.signame = signal.Signals(signum).name
            self._t_first = now
            print(f"[preempt] {self.signame} received — draining: finishing "
                  f"the in-flight chunk, committing, writing a final "
                  f"snapshot (send again in >{FORCE_GRACE_S:.0f}s to force "
                  f"exit)", file=sys.stderr, flush=True)

    def install(self) -> "DrainHandler":
        for sig in _DRAIN_SIGNALS:
            self._prev[sig] = signal.signal(sig, self._handle)
        return self

    def uninstall(self) -> None:
        for sig, prev in self._prev.items():
            signal.signal(sig, prev)
        self._prev.clear()
