"""Reduction of a profiler trace to what the metric readers need.

``profile(dir)`` records the window with ``jax.profiler``; ``reduce(dir,
run)`` reads the ``.xplane.pb`` it wrote with ``jax.profiler.ProfileData``
and keeps, inside the harness's ``chipbench.window`` span:

- per chip (planes ``/device:TPU:<n>``), the program executions on the
  ``XLA Modules`` line and the operations on the ``XLA Ops`` line, each
  as ``(name, start_ns, end_ns)``;
- the harness's host spans (``chipbench.<name>`` on any host line).

Busy time is the union of a chip's operation intervals (its module
intervals where the trace has no operations), averaged over the chips;
idle is the rest of the window. Each idle gap is named by the innermost
harness span the host was in at its middle.
"""
from __future__ import annotations

import contextlib
import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"

Event = Tuple[str, int, int]             # name, start_ns, end_ns


@contextlib.contextmanager
def profile(tdir):
    """Trace what runs inside, writing under ``tdir``; the harness's window
    span marks the traced window on the trace's own clock."""
    import jax
    os.makedirs(tdir, exist_ok=True)
    jax.profiler.start_trace(str(tdir))
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def union_ns(intervals) -> int:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The stretches of ``[lo, hi)`` that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


class Reduced:
    """The traced window, reduced (see module doc)."""

    def __init__(self, window: Tuple[int, int], chips: Dict[str, dict],
                 spans: List[Event], dropped: bool = False):
        self.window = window
        self.chips = chips                  # plane name -> {modules, ops}
        self.spans = spans
        self.dropped = dropped              # the profiler lost events

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, chip: dict) -> List[Tuple[int, int]]:
        evs = chip["ops"] or chip["modules"]
        return [(s, e) for _, s, e in evs]

    @property
    def busy_s(self) -> float:
        if not self.chips:
            return 0.0
        return sum(union_ns(self._busy(c)) for c in self.chips.values()) \
            / len(self.chips) / 1e9

    def events(self, line: str, match: Callable[[str], bool]
               ) -> Dict[str, List[Event]]:
        """Per chip, the events of ``line`` (``"modules"`` or ``"ops"``)
        whose name ``match`` accepts."""
        return {p: [ev for ev in c[line] if match(ev[0])]
                for p, c in self.chips.items()}

    def seconds(self, line: str, match) -> Tuple[float, float]:
        """Mean over chips of the summed duration of matching events, and
        the mean count of them."""
        per = self.events(line, match)
        if not per:
            return 0.0, 0.0
        total = sum(e - s for evs in per.values() for _, s, e in evs)
        count = sum(len(evs) for evs in per.values())
        return total / len(per) / 1e9, count / len(per)

    def span_at(self, t: int) -> str:
        """The innermost harness span around time ``t``."""
        best = None
        for name, s, e in self.spans:
            if s <= t < e and name != WINDOW_SPAN \
                    and (best is None or s >= best[1]):
                best = (name, s, e)
        return best[0][len(SPAN_PREFIX):] if best else "host (no span)"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (summed over chips,
        per chip on average) and the longest idle gaps of the first chip,
        each named by what the host was doing."""
        tot: Dict[str, int] = {}
        for c in self.chips.values():
            for name, s, e in (c["ops"] or c["modules"]):
                tot[name] = tot.get(name, 0) + (e - s)
        n = max(1, len(self.chips))
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        if self.chips:
            first = self.chips[sorted(self.chips)[0]]
            gs = gaps_ns(self._busy(first), *self.window)
            gs = sorted(gs, key=lambda g: g[0] - g[1])[:top]
            gaps = [[self.span_at((a + b) // 2), (b - a) / 1e9]
                    for a, b in gs]
        return {"device_ops": [[short_name(k), v / n / 1e9]
                               for k, v in ops],
                "idle_gaps": gaps}


def short_name(text: str, width: int = 160) -> str:
    """An operation's HLO text cut to its name, shape and opcode."""
    return text if len(text) <= width else text[:width - 3] + "..."


def _clip(evs, lo, hi) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


#: a chip line with more operations than this (a simulator loop traces
#: every operation of every step) keeps only its module executions
MAX_OPS = 400_000


def _line_events(line, cap: Optional[int] = None) -> Optional[List[Event]]:
    out = []
    for ev in line.events:
        if cap is not None and len(out) >= cap:
            return None
        out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


DROPPED = "Trace Buffers Dropped"


def read_xspace(path: str) -> Tuple[Dict[str, dict], List[Event], bool]:
    """Device module/op events per chip plane, harness spans (unclipped),
    and whether the profiler dropped events for want of buffer."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    chips: Dict[str, dict] = {}
    spans: List[Event] = []
    dropped = False
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    chip["modules"] = _line_events(line)
                elif line.name == OP_LINE:
                    chip["ops"] = _line_events(line, MAX_OPS) or []
                elif line.name not in ("Async XLA Ops",):
                    dropped |= any(ev.name == DROPPED for ev in line.events)
            chips[plane.name] = chip
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return chips, spans, dropped


def reduce(tdir, run=None) -> Reduced:
    files = glob.glob(os.path.join(str(tdir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {tdir}, found "
                           f"{len(files)}")
    return reduce_events(*read_xspace(files[0]))


def reduce_events(chips: Dict[str, dict], spans: List[Event],
                  dropped: bool = False) -> Reduced:
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise RuntimeError("the trace holds no window span")
    lo, hi = win[0]
    kept = {p: {k: _clip(v, lo, hi) for k, v in c.items()}
            for p, c in chips.items()}
    return Reduced((lo, hi), kept, [sp for sp in spans
                                    if sp[2] > lo and sp[1] < hi], dropped)
