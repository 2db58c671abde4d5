"""Capture a profile around a block and read back its host events.

    with capture() as cap:
        engine.topk(q, 5)
    cap.named("engine.topk")  # -> [Event(name, start_ns, end_ns, line, args)]

`repro.obs` spans are `jax.profiler.TraceAnnotation`s, so they land in the
capture's `.xplane.pb` next to JAX's own host events (dispatch, compiles)
and the XLA ops of the CPU backend, on one clock.  Events come from every
plane that is not a device plane; `line` tells host threads apart.
"""

from __future__ import annotations

import contextlib
import glob
import os
import tempfile
import warnings
from dataclasses import dataclass

import jax
from jax.profiler import ProfileData


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    line: tuple  # (plane name, line number): one host thread
    args: dict

    def within(self, other: "Event") -> bool:
        return other.start_ns <= self.start_ns and self.end_ns <= other.end_ns


class Capture:
    def __init__(self):
        self.events: list[Event] = []

    def named(self, name: str) -> list[Event]:
        return [e for e in self.events if e.name == name]

    def names(self) -> set:
        return {e.name for e in self.events}

    def load(self, trace_dir: str) -> None:
        path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
        with warnings.catch_warnings():
            # jaxlib's event-stats iterator warns on every read
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in ProfileData.from_file(path).planes:
                if plane.name.startswith("/device:"):
                    continue
                for i, line in enumerate(plane.lines):
                    key = (plane.name, i)
                    self.events.extend(
                        Event(ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns, key,
                              dict(ev.stats)) for ev in line.events)


@contextlib.contextmanager
def capture():
    """Run the block under a profiler session; the yielded Capture holds
    the session's host events once the block has exited."""
    cap = Capture()
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            yield cap
        cap.load(d)
