"""Profiler capture and the reduction from a trace to plain numbers.

`capture` records a JAX profiler trace of a block of work, with the
Python tracer off so that the host loop is not slowed call by call.
`Trace.load` reads the ``.xplane.pb`` it leaves into plain lists:

  device ops    (name, start_ns, end_ns, chip, text) from the
                ``XLA Ops`` line of every ``/device:TPU:<n>`` plane. On a
                TPU an op's event is named by its whole HLO instruction,
                ``%fusion.3 = f32[...] fusion(...), ...``: ``name`` is
                the instruction's name (``fusion.3``) and ``text`` the
                whole of it. A Pallas kernel is a custom call whose text
                holds ``custom_call_target="tpu_custom_call"``;
  host events   (name, start_ns, end_ns, thread) from the
                ``/host:CPU`` plane: the benchmark's own
                ``TraceAnnotation`` spans and JAX's dispatch events.

Both are on the profiler's one clock. The reductions below (busy time
as a union of intervals, idle gaps, kernel time, the breakdown) work on
those lists alone, so a test can check them on a recorded trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import shutil
from collections import defaultdict
from pathlib import Path
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
#: the benchmark's own host spans all start with this
SPAN_PREFIX = "bench."


#: the instruction's name at the head of an HLO op's text
HLO_NAME = re.compile(r"^%?([^\s=]+)\s*=")
#: what marks a Pallas (Mosaic) kernel among the HLO ops
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    where: str          # chip index for device ops, thread for host
    text: str = ""      # a device op's whole HLO instruction

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@contextlib.contextmanager
def capture(out_dir: Path) -> Iterator[Path]:
    """Trace the block into ``out_dir`` (emptied first)."""
    import jax
    out_dir = Path(out_dir)
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        yield out_dir
    finally:
        jax.profiler.stop_trace()


def find_xplane(out_dir: Path) -> Path:
    found = sorted(Path(out_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    return found[-1]


class Trace:
    """Device ops and host events of one trace, on one clock."""

    def __init__(self, device_ops: Sequence[Event],
                 host_events: Sequence[Event]):
        self.device_ops = sorted(device_ops, key=lambda e: e.start_ns)
        self.host_events = sorted(host_events, key=lambda e: e.start_ns)

    @classmethod
    def load(cls, path: Path) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(str(path))
        ops: List[Event] = []
        host: List[Event] = []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == OPS_LINE:
                    ops += [device_op(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      m.group(1))
                            for ev in line.events]
                elif plane.name == HOST_PLANE:
                    host += [Event(ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns, line.name)
                             for ev in line.events if ev.duration_ns > 0]
        return cls(ops, host)

    # -- host spans ---------------------------------------------------------

    def spans(self, name: str) -> List[Event]:
        """The benchmark's host spans called ``name``, in time order."""
        return [e for e in self.host_events if e.name == name]

    def window(self, name: str) -> Interval:
        """The interval of the single span ``name``."""
        found = self.spans(name)
        if len(found) != 1:
            raise ValueError(f"expected one {name!r} span, found "
                             f"{len(found)}")
        return found[0].start_ns, found[0].end_ns

    # -- device time --------------------------------------------------------

    def chips(self) -> List[str]:
        return sorted({e.where for e in self.device_ops}, key=int)

    def ops_in(self, window: Interval,
               chip: Optional[str] = None) -> List[Event]:
        lo, hi = window
        return [e for e in self.device_ops
                if e.end_ns > lo and e.start_ns < hi
                and (chip is None or e.where == chip)]

    def busy_ns(self, window: Interval) -> float:
        """Union of device-op intervals in ``window``, averaged over the
        chips that ran any op."""
        chips = self.chips()
        if not chips:
            return 0.0
        return sum(union_ns(((e.start_ns, e.end_ns)
                             for e in self.ops_in(window, c)), window)
                   for c in chips) / len(chips)

    def kernel_ns(self, window: Interval,
                  pattern: "Optional[re.Pattern[str]]" = None) -> float:
        """Summed device time of the Pallas kernels (of those whose name
        matches ``pattern``, if given), clipped to ``window``, over all
        chips."""
        lo, hi = window
        return sum(min(e.end_ns, hi) - max(e.start_ns, lo)
                   for e in self.ops_in(window)
                   if is_pallas(e)
                   and (pattern is None or pattern.match(e.name)))

    def idle_gaps(self, window: Interval) -> List[Interval]:
        """Intervals of ``window`` in which no op ran on the first chip."""
        chips = self.chips()
        if not chips:
            return [window]
        return gaps(((e.start_ns, e.end_ns)
                     for e in self.ops_in(window, chips[0])), window)

    def host_label(self, at_ns: float) -> str:
        """What the host was doing at ``at_ns``: the shortest host event
        that covers it, or ``idle host`` where none does."""
        best = None
        for e in self.host_events:
            if e.start_ns > at_ns:
                break
            if e.end_ns >= at_ns and (best is None
                                      or e.dur_ns < best.dur_ns):
                best = e
        return best.name if best is not None else "idle host"

    def breakdown(self, window: Interval, top: int = 10) -> dict:
        """The device ops that took most time (by name, with its numeric
        suffix dropped) and the longest idle gaps, each labelled by what
        the host was doing in its middle."""
        per_op = defaultdict(float)
        lo, hi = window
        for e in self.ops_in(window):
            fam = op_family(e.name) + (" (pallas)" if is_pallas(e) else "")
            per_op[fam] += (min(e.end_ns, hi)
                                          - max(e.start_ns, lo)) * 1e-9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_gaps(window), key=lambda g: g[0] - g[1])
        return {
            "device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[self.host_label(0.5 * (a + b)), (b - a) * 1e-9]
                          for a, b in idle[:top]],
        }


def device_op(text: str, start_ns: float, end_ns: float,
              chip: str) -> Event:
    """A device op's event; ``text`` is its HLO instruction or a name."""
    m = HLO_NAME.match(text)
    return Event(m.group(1) if m else text, start_ns, end_ns, chip, text)


def is_pallas(e: Event) -> bool:
    return PALLAS_TARGET in e.text


def op_family(name: str) -> str:
    """``fusion.12`` -> ``fusion``; a kernel's name without its suffix."""
    return re.sub(r"(\.\d+)+$", "", name)


def union_ns(intervals: Iterable[Interval], window: Interval) -> float:
    """Length of the union of ``intervals`` clipped to ``window``."""
    lo, hi = window
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def gaps(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval covers."""
    lo, hi = window
    out, t = [], lo
    for a, b in sorted(intervals):
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]
