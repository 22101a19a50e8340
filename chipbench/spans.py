#!/usr/bin/env python3
"""The program's own spans in a profiler trace: what the host was doing.

The program marks its layer boundaries with ``jax.profiler``
``TraceAnnotation`` spans named ``<module>.<what>`` (``serve.*``,
``journal.*``, ``qexec.*``, ``segments.*``; ``docs/serving.md`` lists
them).  They land in the same ``.xplane.pb`` as the device planes, on
the same clock, so this module reads them beside
:mod:`chipbench.trace`'s reduction of the same ``window``:

* each span's self time: its duration less what its child spans cover;
* every idle gap of the device in the window, named as
  :mod:`chipbench.trace` names its longest ones (the harness span that
  overlaps it most) and, after a ``/``, the program span that the host
  was innermost in for most of the gap, as in
  ``submit_ingest/journal.append``.  A gap the host spent mostly
  outside any program span keeps the harness span's name alone;
* the idle time split instant by instant under the same kind of name,
  since one gap can run from one program span into the next.

Run on a trace directory (or one ``.xplane.pb``) it prints one JSON
object: the window, the longest idle gaps by name, the idle time summed
by gap name and split instant by instant, and each span's count, total
and self time::

    python3 chipbench/spans.py .chipbench_run/trace
"""
from __future__ import annotations

import dataclasses
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import trace  # noqa: E402

PREFIXES = ("serve.", "journal.", "qexec.", "segments.")
_META = re.compile(r"^(?P<name>[^#]*)#(?P<meta>.*)#$")

# a program span as read: start_ns, end_ns, name, kwargs, thread line
Raw = Tuple[float, float, str, dict, object]


@dataclasses.dataclass(frozen=True)
class Span:
    """One program span, clipped to the window.  Times in ns."""
    start: float
    end: float
    name: str
    args: dict
    parent: int         # index of the enclosing program span, or -1
    self_ns: float      # its duration less its child spans'


@dataclasses.dataclass
class Spans:
    """The program spans of one traced window and its named idle gaps."""
    window_s: float
    spans: List[Span]
    gaps: List[Tuple[float, str]]     # every idle gap: (seconds, name)
    # the idle time instant by instant, by "harness/program" span the
    # host was innermost in (a gap can hold several), longest first
    idle: Dict[str, float]

    def span_s(self, name: str, self_time: bool = True) -> float:
        """Seconds spent in spans named ``name``: their self time, or
        with ``self_time=False`` their whole duration."""
        return sum(s.self_ns if self_time else s.end - s.start
                   for s in self.spans if s.name == name) / 1e9

    def span_count(self, name: str) -> int:
        return sum(1 for s in self.spans if s.name == name)

    def idle_by_span(self) -> Dict[str, float]:
        """The window's idle time, summed by gap name, longest first."""
        by = defaultdict(float)
        for secs, name in self.gaps:
            by[name] += secs
        return dict(sorted(by.items(), key=lambda kv: -kv[1]))


def split_name(raw: str) -> Tuple[str, dict]:
    """``serve.dispatch#qid=3,rows=4#`` -> ``("serve.dispatch",
    {"qid": 3, "rows": 4})``; a name without metadata is returned as
    it is."""
    m = _META.match(raw)
    if not m:
        return raw, {}
    args = {}
    for kv in m.group("meta").split(","):
        k, _, v = kv.partition("=")
        args[k] = int(v) if re.fullmatch(r"-?\d+", v) else v
    return m.group("name"), args


def nest(raw: Sequence[Raw], lo: float, hi: float) -> List[Span]:
    """Program spans clipped to ``[lo, hi]``, in order of start, each
    with its parent and self time; a span's children are the spans it
    encloses on its own thread line."""
    rows = sorted(((max(s, lo), min(e, hi), name, args, line)
                   for s, e, name, args, line in raw
                   if min(e, hi) > max(s, lo)),
                  key=lambda r: (r[0], -r[1]))
    parent = [-1] * len(rows)
    own = [e - s for s, e, *_ in rows]
    open_ = defaultdict(list)          # thread line -> enclosing spans
    for i, (s, e, _, _, line) in enumerate(rows):
        stack = open_[line]
        while stack and rows[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
            own[stack[-1]] -= e - s
        stack.append(i)
    return [Span(s, e, n, a, parent[i], max(own[i], 0.0))
            for i, (s, e, n, a, _) in enumerate(rows)]


def _sweep(gaps, spans):
    """For each of the disjoint, time-ordered ``gaps``, the ``spans``
    (tuples that start with start and end, sorted by start) that overlap
    it.  A span that ends before a gap starts cannot overlap a later
    gap, so only the spans still open are kept."""
    open_, j = [], 0
    for gs, ge in gaps:
        while j < len(spans) and spans[j][0] < ge:
            open_.append(spans[j])
            j += 1
        open_ = [sp for sp in open_ if sp[1] > gs]
        yield open_


def _last_open(a, b, open_):
    """The span of ``open_`` that holds all of ``[a, b]`` and started
    last, the later listed of two that start together (the innermost,
    on one thread: a parent is listed before its children), or None."""
    held = [sp for sp in open_ if sp[0] <= a and sp[1] >= b]
    return max(held, key=lambda sp: (sp[0], sp[2]), default=None)


def name_gaps(gaps, host: Sequence[trace.Span], program: Sequence[Span]
              ) -> Tuple[List[str], Dict[str, float]]:
    """Each gap's name (gaps are ns intervals, disjoint, in time order):
    the harness span overlapping it most, the first in ``host`` on a
    tie, as :func:`chipbench.trace.reduce` names the longest; then ``/``
    and the program span the host was innermost in for most of the
    gap, unless it was in none for longer.  Also the idle time split
    instant by instant, by innermost harness span and program span."""
    harness = sorted((s, e, i, nm) for i, (s, e, nm) in enumerate(host)
                     if nm != trace.WINDOW)
    progs = [(p.start, p.end, i, p.name) for i, p in enumerate(program)]
    names, idle = [], defaultdict(float)
    for (gs, ge), h_open, p_open in zip(gaps, _sweep(gaps, harness),
                                        _sweep(gaps, progs)):
        best, who = 0.0, "none"
        for s, e, _, nm in sorted(h_open, key=lambda sp: sp[2]):
            if min(e, ge) - max(s, gs) > best:
                best, who = min(e, ge) - max(s, gs), nm
        cuts = sorted({gs, ge} | {t for sp in h_open + p_open
                                  for t in sp[:2] if gs < t < ge})
        inside = defaultdict(float)
        for a, b in zip(cuts, cuts[1:]):
            h, pr = _last_open(a, b, h_open), _last_open(a, b, p_open)
            key = (h[3] if h else "none") + (f"/{pr[3]}" if pr else "")
            idle[key] += (b - a) / 1e9
            inside[pr[3] if pr else None] += b - a
        top = max(inside, key=inside.get)
        names.append(who + (f"/{top}" if top else ""))
    return names, dict(sorted(idle.items(), key=lambda kv: -kv[1]))


def reduce(ops: Sequence[trace.Op], host: Sequence[trace.Span],
           raw: Sequence[Raw]) -> Spans:
    """Device operations, harness spans and program spans on one clock
    (ns) to the :class:`Spans` of the ``window`` span."""
    wins = [(s, e) for s, e, n in host if n == trace.WINDOW]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = wins[0]
    busy = trace.union(trace.clip(((o.start, o.end) for o in ops), lo, hi))
    gaps, at = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    program = nest(raw, lo, hi)
    names, idle = name_gaps(gaps, host, program)
    return Spans(window_s=(hi - lo) / 1e9, spans=program,
                 gaps=[((e - s) / 1e9, n) for (s, e), n in zip(gaps, names)],
                 idle=idle)


def host_events(path) -> Tuple[List[trace.Span], List[Raw]]:
    """The harness spans and the program spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    host, raw = [], []
    data = ProfileData.from_file(str(path))
    for pi, plane in enumerate(data.planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, ln in enumerate(plane.lines):
            for ev in ln.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name in trace.HOST_SPANS:
                    host.append((ev.start_ns, end, ev.name))
                    continue
                name, args = split_name(ev.name)
                if name.startswith(PREFIXES):
                    args.update({k: v for k, v in trace._stats(ev).items()
                                 if isinstance(v, (int, str))})
                    raw.append((ev.start_ns, end, name, args, (pi, li)))
    return host, raw


def newest(path) -> Path:
    """``path`` itself, or the newest ``.xplane.pb`` under it."""
    p = Path(path)
    if p.is_dir():
        found = sorted(p.glob("**/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {p}")
        p = found[-1]
    return p


def load(path) -> Tuple[trace.Summary, Spans]:
    """One trace read twice: :func:`chipbench.trace.load`'s summary of
    the window, and its program spans with every idle gap named."""
    p = newest(path)
    summary = trace.load(p)
    host, raw = host_events(p)
    return summary, reduce(summary.ops, host, raw)


def report(summary: trace.Summary, spans: Spans) -> dict:
    by = defaultdict(lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0})
    for s in spans.spans:
        row = by[s.name]
        row["count"] += 1
        row["total_s"] += (s.end - s.start) / 1e9
        row["self_s"] += s.self_ns / 1e9
    return {"window_s": summary.window_s, "busy_s": summary.busy_s,
            "idle_s": sum(secs for secs, _ in spans.gaps),
            "program_calls": summary.program_calls,
            "idle_gaps": [[n, secs] for secs, n in
                          sorted(spans.gaps, reverse=True)[:trace.TOP]],
            "idle_by_span": spans.idle_by_span(),
            "idle_split": spans.idle,
            "spans": dict(sorted(by.items()))}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-1], file=sys.stderr)
        return 2
    print(json.dumps(report(*load(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
