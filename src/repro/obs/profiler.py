"""Programmatic ``jax.profiler`` windows keyed on the global step counter.

``--profile-steps A:B`` opens a profiler trace just before the chunk
dispatch that contains global step A and closes it after the first chunk
boundary at or past B — profiling exactly the steady-state steps you asked
for instead of hand-timing around warmup/compile. The trace lands in
``log_dir`` in TensorBoard/Perfetto format (``jax.profiler.start_trace``).

The window piggybacks on the train loop's existing chunk boundaries: it
adds zero host syncs and zero dispatches of its own. A trace that was asked
for and cannot start or stop raises: a run that silently lost its profile
would be read as one that had none to take.
"""
from __future__ import annotations

from typing import Optional, Tuple

from repro.obs.recorder import Recorder, get_recorder


def parse_profile_steps(spec: str) -> Tuple[int, int]:
    """``"A:B"`` -> (A, B), validated (0 <= A < B)."""
    try:
        a_txt, b_txt = spec.split(":")
        a, b = int(a_txt), int(b_txt)
    except ValueError:
        raise ValueError(
            f"--profile-steps wants 'START:STOP' (global steps), got {spec!r}")
    if a < 0 or b <= a:
        raise ValueError(
            f"--profile-steps needs 0 <= START < STOP, got {spec!r}")
    return a, b


class ProfileWindow:
    """Open a ``jax.profiler`` trace around chosen chunk dispatches.

    The trainer calls :meth:`before_chunk` with the global step the next
    chunk starts at, and :meth:`after_chunk` with the step it ended at; the
    window starts the trace at the first chunk containing ``start_step``
    and stops it at the first boundary >= ``stop_step`` (or on ``close``,
    so a profile window spanning the end of training still flushes).
    """

    def __init__(self, start_step: int, stop_step: int, log_dir: str,
                 recorder: Optional[Recorder] = None):
        if not 0 <= start_step < stop_step:
            raise ValueError(f"need 0 <= start < stop, got "
                             f"({start_step}, {stop_step})")
        self.start_step = int(start_step)
        self.stop_step = int(stop_step)
        self.log_dir = log_dir
        self.recorder = recorder
        self.active = False
        self.done = False

    def _rec(self) -> Recorder:
        return self.recorder if self.recorder is not None else get_recorder()

    def before_chunk(self, next_step: int) -> None:
        if self.done or self.active or next_step < self.start_step:
            return
        import jax.profiler

        jax.profiler.start_trace(self.log_dir)
        self.active = True
        self._rec().event("profile_start", step=next_step,
                          log_dir=self.log_dir)

    def after_chunk(self, reached_step: int) -> None:
        if not self.active or reached_step < self.stop_step:
            return
        self._stop(reached_step)

    def close(self, reached_step: Optional[int] = None) -> None:
        """Stop a still-open trace (training ended inside the window)."""
        if self.active:
            self._stop(self.stop_step if reached_step is None
                       else reached_step)

    def _stop(self, step: int) -> None:
        import jax.profiler

        self.active = False
        self.done = True
        jax.profiler.stop_trace()
        self._rec().event("profile_stop", step=step, log_dir=self.log_dir)
