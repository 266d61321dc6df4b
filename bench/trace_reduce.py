"""Profiler trace (``.xplane.pb``) -> device busy time, kernel time by
name, collective time, and the ``breakdown`` of a traced run.

Read with ``jax.profiler.ProfileData`` only.  Device operations are the
events of each device plane's ``XLA Ops`` line (``/device:TPU:<n>``),
named by their HLO instruction (``%gmm.20 = bf16[...] custom-call(...)``:
the op is ``gmm.20``, of kind ``gmm``; a Pallas kernel's kind is its
``pallas_call`` name).  Each op belongs to the program whose execution
on the ``XLA Modules`` line holds it (``jit__lambda(<fingerprint>)``,
one run per execution).  Control-flow ops (``while``, ``conditional``,
``call``) hold other ops and are left out of times by kind.  A trace
recorded on the CPU has no device plane; its XLA operations are the
host events that carry an ``hlo_op`` statistic (with ``program_id`` and
``run_id``), and they count as one device, so the reduction can be
tested without a chip.

All times are seconds.  The window is the host span the benchmark wraps
around the traced steps (``bench.window``); operations are clipped to it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."


CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str                    # HLO instruction name, e.g. "gmm.20"
    start: float
    end: float
    program: object = None       # the compiled program it ran in
    run: object = None           # that program's execution

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def kind(self) -> str:
        """The name without its instance number: ``gmm.20`` -> ``gmm``."""
        head, _, tail = self.name.rpartition(".")
        return head if head and tail.isdigit() else self.name

    def matches(self, *kinds) -> bool:
        return self.kind in kinds


@dataclasses.dataclass
class Trace:
    devices: list            # one list of Op per device
    spans: list              # host spans: (name, start, end)
    window: tuple            # (start, end)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(union([(o.start, o.end) for o in ops])
                   for ops in self.devices) / len(self.devices)

    def ops(self):
        for ops in self.devices:
            yield from ops

    def time_of(self, *words, program=None) -> float:
        """Summed device seconds of the ops of these kinds, averaged over
        devices (only in ``program``, a set of programs, when given)."""
        tot = sum(o.dur for o in self.ops() if o.matches(*words)
                  and (program is None or o.program in program))
        return tot / max(len(self.devices), 1)

    def programs_with(self, *words) -> collections.Counter:
        """program -> number of its executions that ran an op of these
        kinds (counted on the first device)."""
        seen = collections.Counter()
        runs = set()
        for o in (self.devices[0] if self.devices else []):
            if o.matches(*words):
                runs.add((o.program, o.run))
        for prog, _ in runs:
            seen[prog] += 1
        return seen

    def exposed(self, *words) -> float:
        """Seconds of ops of these kinds during which no other op ran on
        that device, averaged over devices."""
        tot = 0.0
        for ops in self.devices:
            other = union_list([(o.start, o.end) for o in ops
                                if not o.matches(*words)
                                and o.kind not in CONTAINERS])
            for o in ops:
                if o.matches(*words):
                    tot += o.dur - overlap(o.start, o.end, other)
        return tot / max(len(self.devices), 1)


def union_list(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union(iv) -> float:
    """Length of the union of intervals."""
    return sum(e - s for s, e in union_list(iv))


def overlap(s, e, merged) -> float:
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in merged)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans, host_ops = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = sorted(((ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9,
                            ev.name, _stats(ev).get("run_id"))
                           for ev in (lines["XLA Modules"].events
                                      if "XLA Modules" in lines else [])))
            ops = sorted((_op(ev, {}) for ev in lines["XLA Ops"].events),
                         key=lambda o: o.start)
            _attribute(ops, mods)
            devices.append(ops)
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(HOST_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
                    else:
                        st = _stats(ev)
                        if "hlo_op" in st:
                            host_ops.append(_op(ev, st))
    if not devices and host_ops:
        devices = [sorted(host_ops, key=lambda o: o.start)]
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        window = (win[0][1], win[0][2])
    else:
        allops = [o for ops in devices for o in ops]
        window = ((min(o.start for o in allops), max(o.end for o in allops))
                  if allops else (0.0, 0.0))
    devices = [[_clip(o, window) for o in ops
                if o.end > window[0] and o.start < window[1]]
               for ops in devices]
    return Trace(devices=devices, spans=spans, window=window)


def _attribute(ops, mods) -> None:
    """Give each op the program execution (module event) that holds it."""
    i = 0
    for o in ops:
        while i < len(mods) and mods[i][1] < o.start:
            i += 1
        if i < len(mods) and mods[i][0] <= o.start:
            o.program, o.run = mods[i][2], mods[i][3]


def _op(ev, st) -> Op:
    return Op(name=ev.name.split(" = ")[0].lstrip("%"),
              start=ev.start_ns * 1e-9,
              end=(ev.start_ns + ev.duration_ns) * 1e-9,
              program=st.get("program_id"), run=st.get("run_id"))


def _clip(o: Op, window) -> Op:
    return dataclasses.replace(o, start=max(o.start, window[0]),
                               end=min(o.end, window[1]))


def breakdown(t: Trace, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps of
    the first device labelled by the host span that covered them."""
    by_name = collections.Counter()
    for o in t.ops():
        if o.kind not in CONTAINERS:
            by_name[o.name] += o.dur / max(len(t.devices), 1)
    ops = [[n, s] for n, s in by_name.most_common(top)]
    gaps = []
    dev = union_list([(o.start, o.end) for o in
                      (t.devices[0] if t.devices else [])])
    edges = [t.window[0]] + [x for iv in dev for x in iv] + [t.window[1]]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    inner = [s for s in t.spans if s[0] != WINDOW_SPAN]
    labelled = []
    for length, a, b in gaps[:top]:
        best, cover = "none", 0.0
        for name, s, e in inner:
            c = max(0.0, min(b, e) - max(a, s))
            if c > cover:
                best, cover = name, c
        labelled.append([best, length])
    return {"device_ops": ops, "idle_gaps": labelled}
