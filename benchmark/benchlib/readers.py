"""What a metric reader is handed, and the loader that finds a reader by
its metric's name (``benchmark/metrics/<name>.py``, with a function
``read(ctx)`` that returns a number, or None where it finds nothing to
read)."""

from __future__ import annotations

import dataclasses
import importlib.util
import os
from typing import Any, Dict, List, Optional, Tuple

from . import trace
from .loop import Record, Run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Span(tuple):
    """A program span, ``(name, start, end, batch id)`` in monotonic
    seconds, with the program's attributes of it (``lane``, ``sets``, ...)
    in ``attrs``.  It unpacks and compares as that 4-tuple."""

    attrs: Dict[str, Any]

    def __new__(cls, name: str, start: float, end: float, cid: Optional[int] = None,
                attrs: Optional[Dict[str, Any]] = None) -> "Span":
        span = super().__new__(cls, (name, start, end, cid))
        span.attrs = dict(attrs or {})
        return span


@dataclasses.dataclass
class Context:
    run: Run
    setup_s: float
    spans: Optional[List[Span]] = None       # program spans (traced runs)
    device_ops: Optional[list] = None        # device operations (traced runs)
    trace_t: Optional[float] = None          # the trace's start, if after the opening
    _busy: Optional[list] = dataclasses.field(default=None, repr=False)

    @property
    def jobs(self) -> List[Record]:
        return self.run.window

    @property
    def sets(self) -> int:
        return sum(r.sets for r in self.jobs)

    def span_seconds(self, name: str) -> Optional[float]:
        """Summed duration of the spans called ``name`` that start inside
        the window; None without spans."""
        if not self.spans:
            return None
        return sum(b - a for _, a, b, _ in self.window_spans(name))

    def window_spans(self, name: str) -> List[Span]:
        """The spans called ``name`` that start inside the window."""
        lo, hi = self.run.open_t, self.run.close_t
        return [s for s in self.spans or () if s[0] == name and lo <= s[1] < hi]

    def device_window(self) -> Tuple[float, float]:
        """The part of the window the device trace covers: all of it, or
        from ``trace_t`` on."""
        return max(self.run.open_t, self.trace_t or self.run.open_t), self.run.close_t

    @property
    def device_jobs(self) -> List[Record]:
        """The jobs whose verdict returned inside the device window."""
        lo = self.device_window()[0]
        return [r for r in self.jobs if r.done > lo]

    def device_busy(self) -> Optional[List[Tuple[float, float]]]:
        """The union of the device operations' intervals inside the device
        window, computed once; None without device operations in the trace."""
        if not self.device_ops:
            return None
        if self._busy is None:
            self._busy = trace.busy(self.device_ops, *self.device_window())
        return self._busy

    def device_busy_s(self) -> Optional[float]:
        """Device-busy seconds inside the device window; None without
        device operations in the trace."""
        busy = self.device_busy()
        return None if busy is None else trace.total(busy)

    def device_idle(self) -> Optional[float]:
        """The share, in %, of the device window in which no operation ran
        on the device; None where the trace shows it busy for no time."""
        busy = self.device_busy_s()
        if not busy:
            return None
        lo, hi = self.device_window()
        return 100.0 * (1.0 - busy / (hi - lo))


def load(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_all(names: List[str], ctx: Context) -> Dict[str, float]:
    out = {}
    for name in names:
        value = load(name).read(ctx)
        if value is not None:
            out[name] = value
    return out
