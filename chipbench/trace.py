"""From a profiler trace (``.xplane.pb``) to busy and idle time,
per-program time, kernel time and the ``breakdown``.

Device operations are the events of the ``XLA Ops`` lines of the
``/device:TPU:n`` planes, each assigned to the program (``XLA
Modules`` event) whose interval holds it; an event's name there is the
HLO instruction's text, of which the instruction name is kept, and a
Pallas kernel is the ``tpu_custom_call`` among them.  On a backend without device
planes (the CPU, in the tests) they are the host events that carry an
``hlo_op`` stat, with the program from their ``hlo_module`` stat.  The
window is the harness's ``window`` span on the host; every interval is
clipped to it.  Busy time is the union of the operations' intervals,
averaged over the devices that ran an operation in the window (a cell
that holds four chips for a program on one is busy on that one); the
idle gaps are what is left, each named
by the harness span (on the host) that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

WINDOW = "window"
TOP = 10                # idle gaps named, and device ops listed
# the harness's own spans, around each call into a layer of the program
HOST_SPANS = ("window", "step", "submit_ingest", "submit_query", "wait",
              "setup_ingest", "make_queries", "warm_queries")

Span = Tuple[float, float, str]            # start_ns, end_ns, name


@dataclasses.dataclass(frozen=True)
class Op:
    start: float
    end: float
    name: str
    program: str
    kernel: bool = False          # a Pallas kernel (tpu_custom_call)
    device: str = ""              # the plane it ran on


def op_name(text: str) -> str:
    """``%bulk_append.1 = (u32[...]) custom-call(...)`` -> ``bulk_append.1``."""
    return text.split(" = ", 1)[0].lstrip("%")


def program_name(raw: str) -> str:
    """``jit_ingest(12)`` -> ``jit_ingest``."""
    return re.sub(r"\(\d+\)$", "", raw)


def union(intervals) -> List[Tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


@dataclasses.dataclass
class Summary:
    """One traced window, reduced.  Times in seconds."""
    window_s: float
    busy_s: float                      # mean over devices
    devices: int
    ops: List[Op]                      # every device's, clipped
    program_calls: Dict[str, int]
    gaps: List[Tuple[float, str]]      # the TOP longest: (seconds, span)

    def program_s(self, pattern: str) -> float:
        """Device time of the programs whose name matches ``pattern``,
        as the union in time of their operations over every device: the
        wall time in which some chip ran one (a program on four chips
        at once counts once)."""
        rx = re.compile(pattern)
        return _busy([o for o in self.ops if rx.fullmatch(o.program)])

    def calls(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(n for p, n in self.program_calls.items()
                   if rx.fullmatch(p))

    def kernel_s(self, program: str) -> float:
        """Device time of the Pallas kernels inside programs named like
        ``program``: on each device the union of their calls, summed
        over devices (a kernel on four chips at once counts four times),
        the time against which a roofline reader sets every chip's
        bytes."""
        rp = re.compile(program)
        by = defaultdict(list)
        for o in self.ops:
            if o.kernel and rp.fullmatch(o.program):
                by[o.device].append(o)
        return sum(_busy(ops) for ops in by.values())

    def breakdown(self, top: int = TOP) -> dict:
        by = defaultdict(float)
        for o in self.ops:
            by[f"{o.program}/{o.name}"] += (o.end - o.start) / 1e9
        ops = sorted(by.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for s, n in self.gaps[:top]]}


def _busy(ops) -> float:
    return sum(e - s for s, e in union((o.start, o.end) for o in ops)) / 1e9


def reduce(device_ops: Dict[str, List[Op]], host: List[Span],
           program_calls: Dict[str, int]) -> Summary:
    """The pure reduction: per-device operations and host spans on one
    clock (ns) to a :class:`Summary` of the ``window`` span."""
    wins = [(s, e) for s, e, n in host if n == WINDOW]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = wins[0]
    ops, busy, n = [], 0.0, 0
    for name, dev in device_ops.items():
        kept = [Op(s, e, o.name, o.program, o.kernel, name) for o in dev
                for s, e in clip([(o.start, o.end)], lo, hi)]
        ops += kept
        busy += _busy(kept)
        n += bool(kept)
    n = max(n, 1)
    merged = union((o.start, o.end) for o in ops)
    gaps, at = [], lo
    for s, e in merged + [(hi, hi)]:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    leaf = [(s, e, nm) for s, e, nm in host if nm != WINDOW]
    named = []
    for gs, ge in gaps:
        best, who = 0.0, "none"
        for s, e, nm in leaf:
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, who = ov, nm
        named.append(((ge - gs) / 1e9, who))
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / n, devices=n,
                   ops=ops, program_calls=dict(program_calls), gaps=named)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def load(path) -> Summary:
    """Read one ``.xplane.pb`` (or the newest under a trace directory)."""
    from jax.profiler import ProfileData
    p = Path(path)
    if p.is_dir():
        found = sorted(glob.glob(str(p / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {p}")
        p = Path(found[-1])
    data = ProfileData.from_file(str(p))
    device_ops: Dict[str, List[Op]] = {}
    calls: Dict[str, int] = defaultdict(int)
    host: List[Span] = []
    cpu_ops: List[Op] = []
    cpu_runs = defaultdict(set)
    on_tpu = any(re.fullmatch(r"/device:TPU:\d+", p.name)
                 for p in data.planes)
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            mods = sorted((m.start_ns, m.start_ns + m.duration_ns,
                           program_name(m.name))
                          for m in lines.get("XLA Modules", []))
            for _, _, m in mods:
                calls[m] += 1
            ops, j = [], 0
            for ev in sorted(lines.get("XLA Ops", []),
                             key=lambda e: e.start_ns):
                s = ev.start_ns
                while j < len(mods) and mods[j][1] < s:
                    j += 1
                prog = mods[j][2] if j < len(mods) and mods[j][0] <= s \
                    else "?"
                ops.append(Op(s, s + ev.duration_ns, op_name(ev.name), prog,
                              "tpu_custom_call" in ev.name))
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name))
                        continue
                    if on_tpu:
                        continue
                    st = _stats(ev)
                    if "hlo_op" in st and not ev.name.startswith("end: "):
                        prog = program_name(str(st.get("hlo_module", "?")))
                        cpu_ops.append(Op(ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          str(st["hlo_op"]), prog))
                        cpu_runs[prog].add(st.get("run_id"))
    if not device_ops and cpu_ops:
        device_ops = {"/host:CPU": cpu_ops}
        calls = {prog: len(r) for prog, r in cpu_runs.items()}
    return reduce(device_ops, host, calls)

